"""Host spans and device op scopes of the certified throughput path.

Two views of where a solve spends its time, both always on:

* **Host spans.**  ``span(name, **counts)`` times a call-level stage on
  ``time.perf_counter`` and keeps a ``Span`` record (name, start, end,
  parent span, root id, integer ``counts``) in a bounded in-memory
  buffer; ``records()`` copies it and ``clear()`` empties it.  Every span
  of one ``run_sweeps``, ``solve_batch`` or ``optimize`` call shares the
  root's id.
  Each span also opens a ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>``, so in a profiled run it lands in the profiler's host
  plane on the device trace's clock (with no profiler session that is a
  no-op check).  Spans sit at call-level boundaries only: never per
  descent iteration and never inside a jitted function.

  =================== ===================================================
  ``engine.run_sweeps`` the whole sweep call (root)
  ``engine.solve_batch`` one batch solve (root when called directly)
  ``engine.prepare``  coarsening and the ``on_disconnected`` policy
  ``plan.build``      ``BatchPlan.build`` (its shape is ``PlanStats``)
  ``plan.pack``       one chunk's padded arrays and density hints
  ``plan.dispatch``   one chunk's batch-solver call: host work,
                      ``device_put``, jit dispatch, and on a program's
                      first call its tracing and compile (or cache load)
  ``plan.sync``       the host waiting on the device
  ``plan.unpack``     results scattered back into ``InstanceSolve``s
  ``sweep.build``     the sweep's topology and traffic build
  ``graphs.repair``   one multigraph repair; ``iterations`` (swap rounds
                      of a repair that returned), ``stalled`` (1 when the
                      stall break fired)
  ``design.optimize`` one fleet search (root); ``rounds``, ``fleet``,
                      ``runs``
  ``design.propose``  one round's move kernels; ``proposals``,
                      ``restarts`` (random restarts where no kernel applied)
  ``design.rank``     one ranking execute, from the fleet's traffic to its
                      bounds; ``lanes``, ``refilled`` (1 when the round
                      reused the previous plan), ``lane_iters_used`` (each
                      lane's own iterations, summed), ``lane_iters_run``
                      (each lane counted at its chunk's longest: what the
                      device ran)
  ``design.certify``  the final certification execute; ``lanes``,
                      ``lane_iters_used``, ``lane_iters_run``
  =================== ===================================================

* **Device op scopes.**  The solvers wrap the APSP forward, the APSP
  backward and the descent update of every step in ``jax.named_scope``
  (``SCOPES``); the names land in each compiled instruction's
  ``metadata op_name`` and change nothing else.  Every batch-solver
  program is dispatched through ``aotcache.dispatch``, which notes it
  here (``note_program``) with the executable that runs: the AOT cache's
  entry carries its scope map, and a jit program's compile is read back
  from jit's own caches on the first ``op_scopes()``.  That maps each
  instruction name of the optimized HLO to its innermost scope, or
  ``None``; a profiler's device ops are named by that HLO, so the map
  turns a device trace into time per scope.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import re
import threading
import time
from typing import Any, Callable, Hashable, Iterator, Mapping

__all__ = ["Span", "span", "current", "records", "clear", "SCOPES", "scoped",
           "noted", "note_program", "op_scopes", "scopes_of_hlo"]

SCOPES = ("apsp_fwd", "apsp_bwd", "descent_update")
MAX_RECORDS = 1 << 14

_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_ids = itertools.count(1)
_local = threading.local()


@dataclasses.dataclass(slots=True)
class Span:
    """One closed (or, while open, running) host span; times in seconds
    of ``time.perf_counter``."""

    name: str
    start: float
    end: float | None
    id: int
    parent: int | None
    root: int
    counts: dict[str, int]

    def set(self, **counts: int) -> None:
        """Record integer attributes known only once the work ran."""
        self.counts.update({k: int(v) for k, v in counts.items()})


@contextlib.contextmanager
def span(name: str, **counts: int) -> Iterator[Span]:
    """Time the enclosed block as span ``name``; yields its ``Span``.
    As a decorator it times each call (``current()`` is its ``Span``)."""
    import jax
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    sid = next(_ids)
    parent = stack[-1] if stack else None
    rec = Span(name, time.perf_counter(), None, sid,
               parent.id if parent else None,
               parent.root if parent else sid,
               {k: int(v) for k, v in counts.items()})
    stack.append(rec)
    try:
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            yield rec
    finally:
        rec.end = time.perf_counter()
        stack.pop()
        _records.append(rec)


def current() -> Span | None:
    """The innermost open span of this thread, or ``None``."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def records() -> list[Span]:
    """The closed spans still in the buffer, oldest first (a copy)."""
    return list(_records)


def clear() -> None:
    _records.clear()


# ---------------------------------------------------------------------------
# device op scopes
# ---------------------------------------------------------------------------

def scoped(scope: str):
    """Decorator: trace the function under ``jax.named_scope(scope)``
    (a fresh scope object per call, so nested and threaded tracing are
    safe).  Changes HLO metadata only."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            import jax
            with jax.named_scope(scope):
                return fn(*args, **kwargs)
        return inner
    return wrap


# signature key -> the program's instruction -> scope map, or (until the
# first op_scopes()) a callable giving its compiled executable
_programs: dict[Hashable, Mapping[str, str | None] | Callable[[], Any]] = {}


def noted(key: Hashable) -> bool:
    """Whether a program was noted under ``key``."""
    return key in _programs


def note_program(key: Hashable, program: Mapping[str, str | None]
                 | Callable[[], Any]) -> None:
    """Note one dispatched program under its signature ``key``: its scope
    map (``scopes_of_hlo``), or a callable returning its compiled
    executable, read on the first ``op_scopes()``.  The first note of a
    key holds."""
    _programs.setdefault(key, program)


_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"(?:condition|body|to_apply|calls|true_computation|"
                    r"false_computation)=%?([\w.\-]+)|"
                    r"branch_computations=\{([^}]*)\}")


def _scope_of(op_name: str) -> str | None:
    """The innermost of ``SCOPES`` named in an ``op_name`` path."""
    best, at = None, -1
    for scope in SCOPES:
        for m in re.finditer(rf"(?<![\w.]){scope}(?![\w.])", op_name):
            if m.start() > at:
                best, at = scope, m.start()
    return best


def scopes_of_hlo(text: str) -> dict[str, str | None]:
    """Instruction name -> innermost scope (or ``None``) for every
    instruction of one optimized HLO module's text.

    An instruction whose ``op_name`` names a scope is in that scope.  One
    that names none, or has no metadata (the copies and loop plumbing
    the compiler adds), is in the scope of the instruction that calls its
    computation (a ``while``, ``conditional``, ``call`` or fusion), and
    the entry computation's are in none."""
    comps: dict[str, list[tuple[str, str | None, list[str]]]] = {}
    entry, body = None, None
    for line in text.splitlines():
        head = _HEADER.match(line)
        if head and not line[:1].isspace():
            body = comps.setdefault(head.group(2), [])
            if head.group(1):
                entry = head.group(2)
            continue
        m = _INSTR.match(line)
        if m is None or body is None:
            continue
        op_name = _OP_NAME.search(line)
        called = []
        for one, many in _CALLS.findall(line):
            called += [one] if one else [
                c.strip().lstrip("%") for c in many.split(",") if c.strip()]
        body.append((m.group(1), _scope_of(op_name.group(1))
                     if op_name else None, called))
    out: dict[str, str | None] = {}
    inherited: dict[str, str | None] = {entry: None} if entry else {}
    todo = [entry] if entry else []
    while todo:
        comp = todo.pop()
        for name, own, called in comps.get(comp, []):
            scope = own or inherited[comp]
            out[name] = scope
            for c in called:
                if c not in inherited:
                    inherited[c] = scope
                    todo.append(c)
                elif inherited[c] != scope and inherited[c] is not None:
                    inherited[c] = None
                    todo.append(c)
    for instrs in comps.values():      # computations no entry reaches
        for name, own, _ in instrs:
            out.setdefault(name, own)
    return out


def op_scopes() -> dict[str, str | None]:
    """Instruction name -> scope (``SCOPES``, or ``None``) over every
    program the batch solvers dispatched in this process, read from each
    executable's optimized HLO.  A name that two programs place in
    different scopes maps to ``None``."""
    out: dict[str, str | None] = {}
    for key, program in list(_programs.items()):
        if callable(program):
            program = _programs[key] = scopes_of_hlo(program().as_text())
        for name, scope in program.items():
            if name in out and out[name] != scope:
                out[name] = None
            else:
                out[name] = scope
    return out

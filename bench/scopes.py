"""The system's own spans and op scopes, as the per-layer metrics read them.

The program keeps host spans (``repro.core.spans.records()``: name, start
and end on ``time.perf_counter``, the clock of the benchmark's own spans)
and maps each instruction of every program its batch solvers dispatched
to a device scope (``repro.core.spans.op_scopes()``: ``apsp_fwd``,
``apsp_bwd``, ``descent_update``, or ``None`` for set-up and loop
control), read from the compiled programs' own HLO.  A profiler's device
ops are named by that HLO, so ``Op.short``'s head (the instruction name,
less a ``[custom_call_target]`` suffix) is looked up in the map.

A program that keeps neither (one older than its spans) gives ``None``
here, and every reader then reports nothing; so does a span buffer that
may have dropped a span the reader needs.  So does a device map that
fails an op of the trace, or leaves more than ``UNSCOPED_MAX`` of the
busy time unscoped: a wrong share is worse than none.
"""
from __future__ import annotations

from bench import trace

UNSCOPED_MAX = 0.10    # largest share of busy time left in no scope

_busy: dict[int, tuple[object, dict | None]] = {}


def _spans_module():
    try:
        from repro.core import spans
    except ImportError:
        return None
    return spans


def program_spans(kept_after: float | None = None) -> list | None:
    """The program's closed host spans, or ``None`` where it keeps none.
    Its buffer is bounded and drops the oldest spans first: a full buffer
    gives ``None`` too, unless its oldest span closed by ``kept_after``
    (then every span closed later is still there)."""
    spans = _spans_module()
    if spans is None:
        return None
    recs = spans.records()
    if len(recs) >= spans.MAX_RECORDS and (
            kept_after is None or recs[0].end > kept_after):
        return None
    return recs


def op_scopes() -> dict | None:
    """The program's instruction -> scope map, or ``None``."""
    spans = _spans_module()
    return None if spans is None else spans.op_scopes()


def span_intervals(names, before: float | None = None,
                   kept_after: float | None = None) -> list | None:
    """``(start, end)`` of the program's spans named in ``names`` (and,
    with ``before``, closed by then), or ``None`` where it keeps none or
    may have dropped one (``program_spans``)."""
    recs = program_spans(kept_after)
    if recs is None:
        return None
    return [(r.start, r.end) for r in recs if r.name in names
            and (before is None or r.end <= before)]


def host_pct(run, names) -> float | None:
    """Share of the timed calls' wall time inside the program's spans
    named in ``names`` (their union, clipped to the calls)."""
    calls = run.call_spans()
    total = sum(b - a for a, b in calls)
    if total <= 0:
        return None
    spans = span_intervals(names, kept_after=min(a for a, _ in calls))
    if not spans:
        return None
    inside = trace.length(trace.clip(spans, calls))
    if inside <= 0:
        return None
    return 100.0 * inside / total


def scope_busy(run) -> dict | None:
    """Exclusive device time (ns) per scope, ``None`` the unscoped part,
    over the traced part of the timed calls on the cell's chips; or
    ``None`` where the map fails an op or leaves too much unscoped."""
    hit = _busy.get(id(run))
    if hit is not None and hit[0] is run:
        return hit[1]
    out = _scope_busy(run)
    _busy[id(run)] = (run, out)
    return out


def _scope_busy(run) -> dict | None:
    if run.trace is None:
        return None
    scopes = op_scopes()
    if not scopes:
        return None
    window = trace.clip(run.traced_calls, [run.traced_ns])
    out: dict = {}
    for i in sorted(run.trace.devices)[:run.wl["chips"]]:
        own = trace.self_times(run.trace.devices[i], window)
        for short, ns in own.items():
            head = short.split("[", 1)[0]
            if head not in scopes:
                return None
            out[scopes[head]] = out.get(scopes[head], 0.0) + ns
    busy = sum(out.values())
    if busy <= 0 or out.get(None, 0.0) > UNSCOPED_MAX * busy:
        return None
    return out


def scope_pct(run, scope: str) -> float | None:
    """Share of the device's exclusive busy time spent in ``scope``."""
    busy = scope_busy(run)
    if busy is None:
        return None
    return 100.0 * busy.get(scope, 0.0) / sum(busy.values())

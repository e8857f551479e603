"""One surface for the batched solvers: their table and their runner.

Every batched solver is one ``BatchSolver`` record in ``SOLVERS``: the
per-lane function of one (possibly padded) instance, the result type
whose fields are that function's outputs in order, which field is the
headline bound, and, for the routing solvers, a host hook that adds
per-lane inputs and statics (ECMP's propagation depth ``hops``; KSP's
path tensor and ``hops``).  ``run(name, caps, dems, ...)`` is the body
of every public ``solve_*_batch`` (``mcf.solve_dual_batch``,
``mcf.solve_dual_demgrad_batch``, ``primal.solve_primal_batch``,
``routing.solve_ecmp_batch``, ``routing.solve_ksp_batch``), and
``plan.SOLVERS`` dispatches plan chunks through it.

Each solver gets one plain and one donated ``jax.jit`` of one vmapped
program: per-lane inputs at axis 0, the step size and tolerance shared,
caps/dems donated by the planner (which owns its device buffers) and
never by a direct call, whose caller may reuse them.  The per-lane
function is looked up in its module when a program is traced, not when
this module is imported, so a replacement planted there (a fault test)
reaches every program traced after it.  Programs run through
``aotcache.dispatch`` under the AOT tag ``(name, "donated" | "plain")``.

The records name their module's attributes instead of holding them:
``mcf``, ``primal`` and ``routing`` import this module, so it cannot
import them.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aotcache
from repro.core.apsp import normalize_backend, resolve_backend_density
from repro.core.graphs import as_cap
from repro.kernels import ops as kops

__all__ = ["BatchSolver", "SOLVERS", "run", "jit_cache_size",
           "compile_cache_sizes"]

# the solver statics, all compile-key material (the AOT cache keys on
# their repr), including the ell-bf table width (d_max) and
# relaxation-round cap (max_rounds)
_STATIC = ("iters", "check_every", "backend", "interpret", "d_max",
           "max_rounds")


@dataclasses.dataclass(frozen=True)
class BatchSolver:
    """One batched solver.  ``lane``, ``result`` and ``extra`` name
    attributes of ``repro.core.<module>``: the per-lane function
    ``(cap, dem, n_valid, *extra inputs, lr_peak, tol, **statics)``, the
    batch result dataclass (its fields in the lane's output order), and
    the hook ``(caps, n_valid, **kw) -> (extra inputs, statics)`` that
    adds the ``statics`` names."""

    name: str                # plan.SOLVERS key, first part of the AOT tag
    module: str
    lane: str
    result: str
    value: str               # the result field that is the headline bound
    counter: str             # its plan.compile_cache_sizes key
    extra: str | None = None
    statics: tuple[str, ...] = ()

    def home(self):
        return importlib.import_module(f"repro.core.{self.module}")


SOLVERS = {s.name: s for s in (
    BatchSolver("dual", "mcf", "_solve_one", "DualBatchResult",
                "throughput_ub", "dual.solve_batch"),
    BatchSolver("primal", "primal", "_solve_one", "PrimalBatchResult",
                "throughput_lb", "primal.solve_batch"),
    BatchSolver("dual-demgrad", "mcf", "_solve_one_demgrad",
                "DualDemgradBatchResult", "throughput_ub",
                "dual.solve_demgrad_batch"),
    BatchSolver("ecmp", "routing", "_ecmp_one", "RoutingBatchResult",
                "throughput_lb", "routing.ecmp_batch",
                extra="_ecmp_extra", statics=("hops",)),
    BatchSolver("ksp", "routing", "_ksp_one", "RoutingBatchResult",
                "throughput_lb", "routing.ksp_batch",
                extra="_ksp_extra", statics=("hops",)),
)}


def _jit_pair(spec: BatchSolver):
    def lanes(*args, **statics):
        fn = getattr(spec.home(), spec.lane)   # at trace time (docstring)
        in_axes = (0,) * (len(args) - 2) + (None, None)
        return jax.vmap(functools.partial(fn, **statics),
                        in_axes=in_axes)(*args)
    lanes.__name__ = lanes.__qualname__ = \
        f"{spec.name.replace('-', '_')}_batch"
    static = _STATIC + spec.statics
    return (jax.jit(lanes, static_argnames=static),
            jax.jit(lanes, static_argnames=static, donate_argnums=(0, 1)))


_PROGRAMS = {name: _jit_pair(spec) for name, spec in SOLVERS.items()}


def _no_extra(caps, n_valid):
    return (), {}


def _empty(field: str) -> np.ndarray:
    # a lane returns float32 scalars, its int32 iteration count and, for
    # dual-demgrad, an [n, n] demand gradient
    if field == "iterations":
        return np.zeros(0, np.int32)
    return np.zeros((0, 0, 0) if field == "dem_grad" else 0, np.float32)


def run(name: str, caps, dems, *, n_valid=None, iters: int = 800,
        lr: float = 0.08, tol: float = 0.0, check_every: int = 25,
        interpret: bool | None = None, backend: str | None = None,
        aot=None, sharding=None, donate: bool = False, block: bool = True,
        d_max: int | None = None, mean_degree: float | None = None,
        max_rounds: int | None = None, **kw):
    """Solve stacked [R, N, N] capacities/demands with solver ``name``.

    ``caps`` may be a stacked array or a sequence of Topologies/matrices
    of equal size; an empty sequence gives an empty result.  ``n_valid``
    ([R] ints, default N) counts each instance's real leading nodes; the
    rest is padding, masked out of the solve.  ``backend`` names the APSP
    backend (``repro.core.apsp``); ``d_max``/``mean_degree`` are density
    stats that spare the scan of ``caps`` when ``"auto"``/``"ell-bf"``
    may go sparse.  ``sharding`` commits the batch axis across devices
    (R must then be a device-count multiple); ``donate=True`` hands the
    device inputs back to XLA; ``block=False`` returns in-flight device
    arrays (sync with ``jax.block_until_ready``); ``aot`` takes an
    ``aotcache.AotCache`` for unsharded calls.  ``kw`` goes to the
    solver's hook (KSP: ``k``, ``max_hops``, ``hops``; ECMP: ``hops``).
    """
    spec = SOLVERS[name]
    home = spec.home()
    result = getattr(home, spec.result)
    interpret = kops.resolve_interpret(interpret)
    backend = normalize_backend(backend)
    if len(caps) != len(dems):
        raise ValueError(f"caps ({len(caps)}) and dems ({len(dems)}) "
                         "must have equal length")
    if len(caps) == 0:
        return result(*(_empty(f.name) for f in dataclasses.fields(result)))
    if not isinstance(caps, (np.ndarray, jax.Array)):
        caps = np.stack([as_cap(c) for c in caps])
    if not isinstance(dems, (np.ndarray, jax.Array)):
        dems = np.stack([np.asarray(d) for d in dems])
    if n_valid is None:
        n_valid = np.full(caps.shape[0], caps.shape[1], np.int32)
    backend, d_max = resolve_backend_density(
        backend, caps, n=caps.shape[1], d_max=d_max,
        mean_degree=mean_degree)
    hook = getattr(home, spec.extra) if spec.extra else _no_extra
    extra, statics = hook(caps, n_valid, **kw)
    lanes = (jnp.asarray(caps, jnp.float32), jnp.asarray(dems, jnp.float32),
             jnp.asarray(n_valid, jnp.int32)) + tuple(map(jnp.asarray, extra))
    if sharding is not None:
        lanes = jax.device_put(lanes, sharding)
    static_kw = dict(iters=iters, check_every=check_every, backend=backend,
                     interpret=interpret, d_max=d_max,
                     max_rounds=max_rounds, **statics)
    plain, donated = _PROGRAMS[name]
    out = aotcache.dispatch(
        donated if donate else plain, (name, "donated" if donate else "plain"),
        lanes + (jnp.float32(lr), jnp.float32(tol)), static_kw, aot=aot,
        sharding=sharding)
    return result(*(out if not block else map(np.asarray, out)))


def jit_cache_size(*fns) -> int | None:
    """Total compiled-program count of the given jitted callables (one per
    distinct (shape, static-arg) combination), or ``None`` (not 0 — callers
    must not mistake "unavailable" for "no compiles") if the installed jax
    does not expose ``_cache_size``, which is a private API."""
    sizes = [getattr(fn, "_cache_size", None) for fn in fns]
    if not all(callable(s) for s in sizes):
        return None
    return sum(s() for s in sizes)


def compile_cache_sizes() -> dict[str, int | None]:
    """Compiled programs of each solver's jit pair, by its ``counter``."""
    return {spec.counter: jit_cache_size(*_PROGRAMS[name])
            for name, spec in SOLVERS.items()}

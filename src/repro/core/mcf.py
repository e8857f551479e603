"""JAX maximum-concurrent-flow solver via dual (LP-duality) descent.

LP duality for max concurrent flow: with edge lengths l >= 0,

    theta* = min_l  sum_e c_e l_e  /  sum_{(s,t)} dem(s,t) * dist_l(s, t)

Every iterate gives a *certified upper bound* on theta* (scale l so the
demand-weighted distance is 1); at the optimum the bound is tight.  We
minimise the log-ratio with Adam in log-length space.  dist_l is all-pairs
shortest paths via ``repro.core.apsp`` — an ``ApspBackend`` registry
(``"squaring" | "squaring-pallas" | "blocked-fw" | "auto"``) whose shared
custom VJP yields shortest-path-DAG subgradients identically on every
backend.  ``backend`` selects it.

This is the paper's CPLEX replacement that actually scales: it is pure
dense linear algebra, jit/vmap-able over topology batches (the paper's "20
runs per point" becomes one batched solve), and sharding the N x N distance
matrices over a mesh distributes the solve.

Batching over *mixed* topology sizes works by padding every instance up to a
common bucket size and passing per-instance valid node counts (``n_valid``):
padded nodes carry zero capacity, zero demand, and ``_INF`` edge weights, so
they contribute nothing to the dual ratio or its gradient.  The descent loop
is a ``lax.while_loop`` with convergence-based early stopping (relative
improvement of the best bound per ``check_every``-iteration window), so a
batch lane that converges stops updating while slower lanes continue.

``interpret`` controls the Pallas kernel execution mode; ``None`` (the
default) auto-detects from ``jax.default_backend()`` — compiled on TPU,
interpreter elsewhere.

This solver certifies only one side of theta*: every iterate UPPER-bounds
the optimum.  Its primal companion, ``repro.core.primal``, reuses ``apsp``
and the same masking/padding conventions to certify the LOWER side from an
explicit feasible flow, and ``repro.core.plan.BatchPlan`` drives both
through identical buckets/chunks/device shards (``solver="dual"`` /
``"primal"``).

Validation: tests/test_flow.py checks the dual bound converges to the HiGHS
exact optimum within a few percent on paper-scale instances, and
tests/test_conformance.py pins ``primal.lb <= theta_exact <= dual.ub``
across traffic patterns x topology families.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import apsp as apsp_mod
from repro.core import batch
from repro.core.apsp import _INF, normalize_backend, resolve_backend_density
from repro.core.graphs import (Topology, as_cap, connected_components,
                               degree_stats)
from repro.core.spans import scoped
from repro.kernels import ops as kops

__all__ = ["DualResult", "DualBatchResult", "DualDemgradBatchResult",
           "apsp", "solve_dual", "solve_dual_batch",
           "solve_dual_demgrad_batch", "aspl", "drop_disconnected",
           "resolve_backend_density", "_INF"]


@dataclasses.dataclass(frozen=True)
class DualResult:
    """One instance's dual solve: a certified UPPER bound on θ* (the
    max concurrent flow rate per unit demand, dimensionless given
    ``cap``/``dem`` in consistent base line-speed units).  θ* ≤
    ``throughput_ub`` always; equality in the limit."""

    throughput_ub: float      # best certified dual bound on theta*
    final_ratio: float        # ratio at the last iterate (convergence probe)
    iterations: int           # descent steps actually executed (<= cap)


@dataclasses.dataclass(frozen=True)
class DualBatchResult:
    """Per-instance solver outputs of one batched solve.

    Indexing/iteration yield the certified bounds (``throughput_ub``) so the
    object drops into code that treated the old ``np.ndarray`` return value
    as a sequence of bounds.  A ``block=False`` solve carries in-flight
    ``jax.Array``s instead of host arrays (sync with
    ``jax.block_until_ready``).
    """

    throughput_ub: np.ndarray   # [B] best certified dual bound per instance
    final_ratio: np.ndarray     # [B] ratio at each instance's last iterate
    iterations: np.ndarray      # [B] descent steps executed per instance

    def __len__(self) -> int:
        return len(self.throughput_ub)

    def __getitem__(self, i):
        return self.throughput_ub[i]

    def __iter__(self):
        return iter(self.throughput_ub)


@dataclasses.dataclass(frozen=True)
class DualDemgradBatchResult:
    """A batched dual solve that ALSO differentiates the bound w.r.t. the
    demand matrix (the adversarial-traffic search's workhorse).

    ``dem_grad[b]`` is the gradient of the converged log-ratio loss
    ``log D(l*) − log α(l*)`` w.r.t. ``dems[b]``, evaluated at the final
    edge lengths l* — a Danskin supergradient of ``log θ*(dem)``: at the
    dual optimum the bound's dem-sensitivity is ``−dist(s, t)/α`` on
    valid pairs (distances do not depend on demand, so this costs one
    extra APSP forward and NO APSP backward).  Descending ``dem`` along
    it (inside the hose polytope) lowers the achievable throughput.
    """

    throughput_ub: np.ndarray   # [B] best certified dual bound per instance
    final_ratio: np.ndarray     # [B] ratio at each instance's last iterate
    iterations: np.ndarray      # [B] descent steps executed per instance
    dem_grad: np.ndarray        # [B, N, N] d loss / d dem at the final l*

    def __len__(self) -> int:
        return len(self.throughput_ub)


def apsp(w: jax.Array, backend: str | None = "auto",
         interpret: bool | None = None, d_max: int | None = None,
         max_rounds: int | None = None) -> jax.Array:
    """All-pairs shortest paths of a weighted adjacency matrix.  ``w``:
    [N, N] edge lengths (any consistent unit; hops when 1 per edge),
    ``_INF`` for non-edges, 0 diagonal.  Returns [N, N] distances in the
    same unit; unreachable pairs stay ~``_INF`` (compare against
    ``_INF / 2``, never equality).

    ``backend`` names an ``ApspBackend`` (see ``repro.core.apsp``).
    ``d_max``/``max_rounds`` are the
    ``"ell-bf"`` statics (table width / relaxation-round cap).
    Differentiable on every backend — the shared VJP is the
    shortest-path-DAG subgradient both solvers consume."""
    return apsp_mod.apsp(w, normalize_backend(backend), interpret,
                         d_max, max_rounds)


def aspl(cap: Topology | np.ndarray | jax.Array,
         dem: np.ndarray | jax.Array | None = None,
         interpret: bool | None = None,
         on_disconnected: str = "raise", *,
         backend: str | None = None) -> float:
    """Average shortest-path length in hops (demand-weighted if dem given).

    ``cap``: ``Topology`` or [N, N] capacities (only the nonzero pattern
    matters — every present link counts as one hop); ``dem``: optional
    [N, N] weights.  Disconnected pairs are excluded from the average.

    ``on_disconnected`` pins what a demanded-but-disconnected pair means
    (the failure-injection path hits these constantly):

    * ``"raise"`` (default) — ``ValueError``: such a pair's "distance"
      would be the ``_INF`` sentinel, not a meaningful path length.
    * ``"drop"`` — zero that pair's demand and average over what remains
      (graceful degradation: the dropped share of demand is what
      ``drop_disconnected`` reports).  If every demanded pair is
      disconnected the average is over nothing and 0.0 is returned.
    """
    if on_disconnected not in ("raise", "drop"):
        raise ValueError(f"on_disconnected must be 'raise' or 'drop', got "
                         f"{on_disconnected!r}")
    cap_host = np.asarray(as_cap(cap))
    n = cap_host.shape[0]
    # hop-metric probes over big degree-bounded graphs are exactly where
    # the sparse backend pays off — resolve density host-side
    bk, d_max = resolve_backend_density(normalize_backend(backend),
                                        cap_host, n=n)
    cap = jnp.asarray(cap_host, jnp.float32)
    w = jnp.where(cap > 0, 1.0, _INF)
    w = jnp.where(jnp.eye(n, dtype=bool), 0.0, w)
    d = apsp(w, bk, interpret, d_max)
    reachable = d < _INF / 2
    if dem is None:
        mask = (~jnp.eye(n, dtype=bool)) & reachable
        return float(jnp.where(mask, d, 0.0).sum() / mask.sum())
    dem = jnp.asarray(dem, jnp.float32)
    if bool(((dem > 0) & ~reachable).any()):
        if on_disconnected == "raise":
            bad = int(((dem > 0) & ~np.asarray(reachable)).sum())
            raise ValueError(
                f"{bad} demanded (s, t) pair(s) are disconnected; "
                "demand-weighted ASPL is undefined on this topology "
                "(pass on_disconnected='drop' to average over the "
                "routable demand only)")
        dem = jnp.where(reachable, dem, 0.0)
        if float(dem.sum()) == 0.0:
            return 0.0
    d = jnp.where(reachable, d, 0.0)
    return float((d * dem).sum() / dem.sum())


def drop_disconnected(cap: Topology | np.ndarray,
                      dem: np.ndarray) -> tuple[np.ndarray, float]:
    """Zero the demand of every (s, t) pair with no path in ``cap``.

    Returns ``(kept_dem, dropped_fraction)`` where ``dropped_fraction`` is
    the share of the total demand that was zeroed (0.0 on a connected
    topology, 1.0 when nothing is routable).  This is the graceful-
    degradation contract of the lifecycle subsystem: failure scenarios
    never crash a solver or leak an ``_INF`` — unroutable demand is
    dropped here and reported as ``reachable_fraction = 1 - dropped``.
    Reachability is a host-side connected-components pass (cheap), not an
    APSP."""
    labels = connected_components(cap)
    dem = np.asarray(dem, np.float64)
    total = float(dem.sum())
    if total == 0.0:
        return dem.copy(), 0.0
    keep = labels[:, None] == labels[None, :]
    kept = np.where(keep, dem, 0.0)
    return kept, float((total - kept.sum()) / total)


def _dual_ratio(z: jax.Array, cap: jax.Array, dem: jax.Array,
                edge_mask: jax.Array, pair_mask: jax.Array, eye: jax.Array,
                backend: str, interpret: bool,
                d_max: int | None = None, max_rounds: int | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Returns (log-ratio loss, certified bound D(l)/alpha(l)).

    ``pair_mask`` marks (valid, valid) node pairs of a padded instance;
    padded nodes are excluded from both sums: their edges carry ``_INF``
    weight (``edge_mask`` is False there, so also zero ``d_val`` weight) and
    their distances are zeroed before the demand-weighted ``alpha`` sum.
    """
    l = jnp.exp(z)
    w = jnp.where(edge_mask, l, _INF)
    w = jnp.where(eye, 0.0, w)
    dist = apsp(w, backend, interpret, d_max, max_rounds)
    alpha = (dem * jnp.where(pair_mask, dist, 0.0)).sum()
    d_val = (cap * l * edge_mask).sum()
    ratio = d_val / alpha
    return jnp.log(d_val) - jnp.log(alpha), ratio


def _descend(cap: jax.Array, dem: jax.Array, n_valid: jax.Array,
             lr_peak: jax.Array, tol: jax.Array, *, iters: int,
             check_every: int, backend: str, interpret: bool,
             d_max: int | None = None, max_rounds: int | None = None):
    """Masked Adam descent over one (possibly padded) instance: nodes >=
    n_valid are masked out.

    Early stopping: every ``check_every`` steps, stop when the best bound's
    relative improvement over the window falls below ``tol`` (monotone best
    => improvement >= 0, so ``tol=0`` never stops early).  All state updates
    are chosen via the ``lax.while_loop`` carry, so under ``vmap`` converged
    batch lanes hold their state while the remaining lanes keep descending.

    Returns ``(best, it, z, dem_m, loss_of)`` — the running-best bound,
    iteration count, final edge-length logits z, the MASKED demand, and
    the masked ``loss_of(z, dem) -> (loss, ratio)`` closure, so callers
    can evaluate the final ratio and/or differentiate it w.r.t. ``dem``
    at the converged z (what the adversarial-traffic entry does).
    """
    nmax = cap.shape[0]
    node_mask = jnp.arange(nmax) < n_valid
    pair_mask = node_mask[:, None] & node_mask[None, :]
    cap = jnp.where(pair_mask, cap, 0.0)
    dem_m = jnp.where(pair_mask, dem, 0.0)
    edge_mask = (cap > 0) & pair_mask
    eye = jnp.eye(nmax, dtype=bool)
    z0 = jnp.zeros((nmax, nmax), jnp.float32)

    def loss_of(z, dem):
        return _dual_ratio(z, cap, dem, edge_mask, pair_mask, eye,
                           backend, interpret, d_max, max_rounds)

    grad_fn = jax.value_and_grad(lambda z: loss_of(z, dem_m), has_aux=True)

    def cond(state):
        i, _, _, _, _, _, done = state
        return (i < iters) & ~done

    @scoped("descent_update")
    def step(state):
        i, z, m, v, best, ref_best, _ = state
        (_, ratio), g = grad_fn(z)
        best = jnp.minimum(best, ratio)
        # Adam with cosine-decayed lr
        t = i + 1
        lr = lr_peak * 0.5 * (1 + jnp.cos(jnp.pi * i / iters)) + 1e-3
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** t)
        vh = v / (1 - 0.999 ** t)
        z = z - lr * mh / (jnp.sqrt(vh) + 1e-8)
        at_check = t % check_every == 0
        rel_gain = (ref_best - best) / jnp.maximum(best, 1e-30)
        done = at_check & (rel_gain < tol)
        ref_best = jnp.where(at_check, best, ref_best)
        return t, z, m, v, best, ref_best, done

    init = (jnp.int32(0), z0, jnp.zeros_like(z0), jnp.zeros_like(z0),
            jnp.float32(jnp.inf), jnp.float32(jnp.inf), jnp.bool_(False))
    it, z, _, _, best, _, _ = jax.lax.while_loop(cond, step, init)
    return best, it, z, dem_m, loss_of


def _solve_one(cap: jax.Array, dem: jax.Array, n_valid: jax.Array,
               lr_peak: jax.Array, tol: jax.Array, *, iters: int,
               check_every: int, backend: str, interpret: bool,
               d_max: int | None = None, max_rounds: int | None = None
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One (possibly padded) instance (see ``_descend``).

    Returns (best bound, final ratio, iterations executed).
    """
    best, it, z, dem_m, loss_of = _descend(
        cap, dem, n_valid, lr_peak, tol, iters=iters,
        check_every=check_every, backend=backend, interpret=interpret,
        d_max=d_max, max_rounds=max_rounds)
    _, final_ratio = loss_of(z, dem_m)
    best = jnp.minimum(best, final_ratio)
    return best, final_ratio, it


def _solve_one_demgrad(cap: jax.Array, dem: jax.Array, n_valid: jax.Array,
                       lr_peak: jax.Array, tol: jax.Array, *, iters: int,
                       check_every: int, backend: str, interpret: bool,
                       d_max: int | None = None, max_rounds: int | None = None
                       ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``_solve_one`` + the Danskin demand-gradient of the converged bound.

    At the final edge lengths l*, the log-ratio loss's gradient w.r.t.
    ``dem`` is ``−dist_l*(s, t) · pair_mask / α`` — the distances do not
    depend on demand, so ``jax.value_and_grad`` here triggers one extra
    APSP FORWARD (shared with the final-ratio evaluation) and no APSP
    backward.  Padded pairs get exactly zero gradient (``pair_mask``).

    Returns (best bound, final ratio, iterations, dem_grad[N, N]).
    """
    best, it, z, dem_m, loss_of = _descend(
        cap, dem, n_valid, lr_peak, tol, iters=iters,
        check_every=check_every, backend=backend, interpret=interpret,
        d_max=d_max, max_rounds=max_rounds)
    (_, final_ratio), g = jax.value_and_grad(
        lambda d: loss_of(z, d), has_aux=True)(dem_m)
    best = jnp.minimum(best, final_ratio)
    return best, final_ratio, it, g


@functools.partial(jax.jit, static_argnames=batch._STATIC)
def _solve(cap, dem, n_valid, lr_peak, tol, *, iters, check_every,
           backend, interpret, d_max=None, max_rounds=None):
    return _solve_one(cap, dem, n_valid, lr_peak, tol, iters=iters,
                      check_every=check_every, backend=backend,
                      interpret=interpret, d_max=d_max,
                      max_rounds=max_rounds)


def solve_dual(cap: Topology | np.ndarray, dem: np.ndarray, *,
               iters: int = 800, lr: float = 0.08, tol: float = 0.0,
               check_every: int = 25, interpret: bool | None = None,
               backend: str | None = None, aot=None,
               d_max: int | None = None,
               max_rounds: int | None = None) -> DualResult:
    """Certified upper bound on max-concurrent-flow throughput (converges
    to the exact value; see module docstring).  ``cap``: a ``Topology``
    or symmetric [N, N] capacity matrix; ``dem``: [N, N] demand — both in
    units of the base line-speed, so the returned θ bound is the paper's
    dimensionless per-unit-demand rate.  ``iters`` caps the descent;
    ``tol > 0`` stops early once the bound's relative improvement per
    ``check_every``-step window drops below it.  ``backend`` picks the
    APSP backend (``repro.core.apsp.BACKENDS``; default auto).  ``aot``
    is accepted for signature parity with the batch entry point; the
    persistent compile cache only serves batched plans."""
    del aot   # single solves always JIT (plan lanes are the hot path)
    interpret = kops.resolve_interpret(interpret)
    cap_host = as_cap(cap)
    backend, d_max = resolve_backend_density(
        normalize_backend(backend), cap_host, n=cap_host.shape[0],
        d_max=d_max)
    capj = jnp.asarray(cap_host, jnp.float32)
    best, final, it = _solve(
        capj, jnp.asarray(dem, jnp.float32), jnp.int32(capj.shape[0]),
        jnp.float32(lr), jnp.float32(tol), iters=iters,
        check_every=check_every, backend=backend, interpret=interpret,
        d_max=d_max, max_rounds=max_rounds)
    return DualResult(float(best), float(final), int(it))




def solve_dual_batch(caps, dems, **kw) -> DualBatchResult:
    """Batched solve over stacked [R, N, N] topologies/demands (the paper's
    '20 runs per data point' in a single vmapped program); the keywords
    and their defaults are ``repro.core.batch.run``'s.

    ``n_valid`` ([R] ints) marks how many leading nodes of each instance are
    real; the rest are padding (zero capacity/demand) and are masked out of
    the dual ratio.  Size-heterogeneous batches are padded into buckets and
    chunks by ``repro.core.plan.BatchPlan`` (which ``DualEngine.solve_batch``
    delegates to) — one compiled program per (bucket, chunk-shape).
    """
    return batch.run("dual", caps, dems, **kw)


def solve_dual_demgrad_batch(caps, dems, **kw) -> DualDemgradBatchResult:
    """``solve_dual_batch`` + per-instance demand gradients — the
    adversarial-traffic search's inner solve.

    Identical batching/padding/sharding/donation semantics (see
    ``solve_dual_batch``); the extra output ``dem_grad[B, N, N]`` is the
    Danskin gradient of each instance's converged log-ratio bound w.r.t.
    its demand matrix (see ``DualDemgradBatchResult``).  One extra APSP
    forward per instance, no APSP backward.
    """
    return batch.run("dual-demgrad", caps, dems, **kw)

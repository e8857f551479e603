"""Primal Frank–Wolfe max-concurrent-flow solver: certified LOWER bounds.

The dual solver (``repro.core.mcf``) certifies only an *upper* bound on the
max concurrent flow throughput theta*.  This module constructs an explicit
feasible flow and certifies a *lower* bound, closing the bracket — at any
scale, not just where the exact LP is tractable.

How it works:

* **Linearized subproblem = shortest-path routing.**  The Frank–Wolfe
  linear minimization oracle of concurrent-flow routing under edge lengths
  ``l`` is all-or-nothing shortest-path routing: send every demand along
  its l-shortest paths.  Those loads come from ONE vjp through the same
  APSP the dual uses (``repro.core.apsp``'s shared custom VJP is the
  shortest-path-DAG subgradient, ties split evenly, identical on every
  ``ApspBackend``):
  ``loads_e = d alpha(l) / d l_e`` where ``alpha = sum dem * dist_l``.
  Each per-pair contribution is a convex combination of that pair's
  shortest paths, so ``loads`` is a valid fractional routing of the FULL
  demand matrix.
* **Lengths ride the dual descent.**  The iterate's edge lengths are the
  same Adam-on-log-ratio trajectory the dual solver runs; as they approach
  dual-optimal, the shortest-path oracle concentrates on tight edges.  One
  APSP forward + one APSP backward per iteration yields BOTH the dual step
  and the FW direction — every primal solve carries the dual upper bound
  for free (``throughput_ub``), which is what lets
  ``get_engine("certified")`` attach an (lb, ub, gap) bracket from one
  fused program through one ``BatchPlan``.
* **FW step with exact line search.**  ``loads <- (1-g) loads + g sp``
  with ``g`` from a ternary search on the max utilization (convex
  piecewise-linear in ``g``), floored at ``1/(t+1)`` so the averaging
  never stalls at a nonsmooth kink.
* **The certificate.**  Every iterate is a convex combination of routings
  that each carry the full demand, so ``loads / max_util`` is a feasible
  concurrent flow at rate ``1 / max_util``: a certified lower bound.  An
  instance whose demand is not routable (a demanded pair disconnected)
  reports ``lb = 0``.

Batching, padding (``n_valid`` masking), early stopping, ``interpret``
auto-detection, and the donated/sharded/async entry points all mirror
``repro.core.mcf`` — ``repro.core.plan.BatchPlan`` drives this solver
through the same buckets/chunks/device sharding as the dual
(``solver="primal"``).

Validation: tests/test_conformance.py asserts ``lb <= theta_exact <= ub``
with bracket gap < 5% across traffic patterns x topology families.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aotcache
from repro.core.apsp import normalize_backend
from repro.core.graphs import Topology, as_cap
from repro.core.mcf import (_INF, apsp, jit_cache_size,
                            resolve_backend_density)
from repro.core.spans import scoped
from repro.kernels import ops as kops

__all__ = ["PrimalResult", "PrimalBatchResult", "solve_primal",
           "solve_primal_batch", "compile_cache_sizes"]

_LS_STEPS = 24   # ternary-search iterations: (2/3)^24 ~ 6e-5 gamma resolution


@dataclasses.dataclass(frozen=True)
class PrimalResult:
    """One instance's primal solve: a certified LOWER bound on θ* (an
    explicit feasible flow routes every demand at this per-unit-demand
    rate) plus the driving dual descent's free UPPER bound — together a
    provable bracket ``throughput_lb`` ≤ θ* ≤ ``throughput_ub``."""

    throughput_lb: float      # certified lower bound (explicit feasible flow)
    throughput_ub: float      # dual bound from the driving descent (free)
    final_util: float         # max edge utilization of the last averaged flow
    iterations: int           # descent steps actually executed (<= cap)

    @property
    def gap(self) -> float:
        """Relative bracket width (ub - lb) / ub."""
        return (self.throughput_ub - self.throughput_lb) / \
            max(self.throughput_ub, 1e-30)


@dataclasses.dataclass(frozen=True)
class PrimalBatchResult:
    """Per-instance outputs of one batched primal solve.  Indexing and
    iteration yield the certified lower bounds (``throughput_lb``); a
    ``block=False`` solve carries in-flight ``jax.Array``s (sync with
    ``jax.block_until_ready``)."""

    throughput_lb: np.ndarray   # [B] certified lower bound per instance
    throughput_ub: np.ndarray   # [B] dual bound of the driving descent
    final_util: np.ndarray      # [B] max utilization at the last iterate
    iterations: np.ndarray      # [B] descent steps executed per instance

    def __len__(self) -> int:
        return len(self.throughput_lb)

    def __getitem__(self, i):
        return self.throughput_lb[i]

    def __iter__(self):
        return iter(self.throughput_lb)


def _solve_one(cap: jax.Array, dem: jax.Array, n_valid: jax.Array,
               lr_peak: jax.Array, tol: jax.Array, *, iters: int,
               check_every: int, backend: str, interpret: bool,
               d_max: int | None = None, max_rounds: int | None = None
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One (possibly padded) instance: nodes >= n_valid are masked out.

    Early stopping: every ``check_every`` steps, stop once the bracket gap
    (ub - lb) / ub shrank by less than ``tol`` over the window (the gap is
    monotone non-increasing, so ``tol=0`` never stops early).  All state
    updates go through the ``lax.while_loop`` carry, so under ``vmap``
    converged lanes hold their state while the rest keep descending.

    Returns (best lb, best ub, final max utilization, iterations).
    """
    nmax = cap.shape[0]
    node_mask = jnp.arange(nmax) < n_valid
    pair_mask = node_mask[:, None] & node_mask[None, :]
    cap = jnp.where(pair_mask, cap, 0.0)
    dem = jnp.where(pair_mask, dem, 0.0)
    edge_mask = (cap > 0) & pair_mask
    eye = jnp.eye(nmax, dtype=bool)
    safe_cap = jnp.where(edge_mask, cap, 1.0)

    def alpha_of(l):
        w = jnp.where(edge_mask, l, _INF)
        w = jnp.where(eye, 0.0, w)
        dist = apsp(w, backend, interpret, d_max, max_rounds)
        return (dem * jnp.where(pair_mask, dist, 0.0)).sum()

    def umax_of(loads):
        return jnp.max(jnp.where(edge_mask, loads / safe_cap, 0.0))

    def lb_of(umax):
        return jnp.where(umax > 0, 1.0 / jnp.maximum(umax, 1e-30), 0.0)

    # a demanded pair with no path makes the flow unroutable: theta* = 0
    routable = alpha_of(jnp.ones_like(cap)) < _INF / 2

    def cond(state):
        i = state[0]
        done = state[-1]
        return (i < iters) & ~done

    @scoped("descent_update")
    def step(state):
        i, z, m, v, loads, best_lb, best_ub, ref_gap, _ = state
        l = jnp.exp(z)
        alpha, vjp = jax.vjp(alpha_of, l)
        (g_alpha,) = vjp(jnp.ones_like(alpha))
        sp = jnp.where(edge_mask, g_alpha, 0.0)   # FW direction: SP loads
        d_val = (cap * l).sum()
        best_ub = jnp.minimum(best_ub, d_val / alpha)

        # dual Adam step on log D(l) - log alpha(l); d/dz = l * d/dl
        g = l * (cap / d_val - sp / alpha)
        t = i + 1
        lr = lr_peak * 0.5 * (1 + jnp.cos(jnp.pi * i / iters)) + 1e-3
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** t)
        vh = v / (1 - 0.999 ** t)
        z = z - lr * mh / (jnp.sqrt(vh) + 1e-8)

        # FW blend: exact ternary line search on the max utilization.
        # Hoist the two per-edge utilization arrays so each of the 2 *
        # _LS_STEPS evaluations is one fused blend + max, not a fresh
        # masked divide (utilization is linear in the flow, so blending
        # pre-divided arrays is the same function of gamma).
        u_cur = jnp.where(edge_mask, loads / safe_cap, 0.0)
        u_sp = jnp.where(edge_mask, sp / safe_cap, 0.0)

        def blended_umax(gam):
            return jnp.max((1 - gam) * u_cur + gam * u_sp)

        lo, hi = jnp.float32(0.0), jnp.float32(1.0)
        for _ in range(_LS_STEPS):
            m1 = lo + (hi - lo) / 3
            m2 = hi - (hi - lo) / 3
            f1 = blended_umax(m1)
            f2 = blended_umax(m2)
            lo = jnp.where(f1 < f2, lo, m1)
            hi = jnp.where(f1 < f2, m2, hi)
        gamma = jnp.maximum((lo + hi) / 2, 1.0 / (t + 1.0))
        gamma = jnp.where(i == 0, 1.0, gamma)   # first step adopts sp fully
        loads = (1 - gamma) * loads + gamma * sp
        best_lb = jnp.maximum(best_lb, lb_of(blended_umax(gamma)))

        at_check = t % check_every == 0
        gap = (best_ub - best_lb) / jnp.maximum(best_ub, 1e-30)
        done = at_check & (ref_gap - gap < tol)
        ref_gap = jnp.where(at_check, gap, ref_gap)
        return t, z, m, v, loads, best_lb, best_ub, ref_gap, done

    z0 = jnp.zeros((nmax, nmax), jnp.float32)
    init = (jnp.int32(0), z0, jnp.zeros_like(z0), jnp.zeros_like(z0),
            jnp.zeros_like(cap), jnp.float32(0.0), jnp.float32(jnp.inf),
            jnp.float32(jnp.inf), jnp.bool_(False))
    it, _, _, _, loads, best_lb, best_ub, _, _ = \
        jax.lax.while_loop(cond, step, init)
    best_lb = jnp.where(routable, best_lb, 0.0)
    return best_lb, best_ub, umax_of(loads), it


# compile-key statics, kept identical to the dual solver's so primal and
# dual lanes share one AOT-cache key scheme (d_max/max_rounds are the
# ell-bf table width and relaxation-round cap)
_STATIC = ("iters", "check_every", "backend", "interpret", "d_max",
           "max_rounds")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _solve(cap, dem, n_valid, lr_peak, tol, *, iters, check_every,
           backend, interpret, d_max=None, max_rounds=None):
    return _solve_one(cap, dem, n_valid, lr_peak, tol, iters=iters,
                      check_every=check_every, backend=backend,
                      interpret=interpret, d_max=d_max,
                      max_rounds=max_rounds)


def _solve_batch_impl(caps, dems, n_valid, lr_peak, tol, *, iters,
                      check_every, backend, interpret, d_max=None,
                      max_rounds=None):
    fn = functools.partial(_solve_one, iters=iters, check_every=check_every,
                           backend=backend, interpret=interpret,
                           d_max=d_max, max_rounds=max_rounds)
    return jax.vmap(fn, in_axes=(0, 0, 0, None, None))(
        caps, dems, n_valid, lr_peak, tol)


_solve_batch = jax.jit(_solve_batch_impl, static_argnames=_STATIC)
_solve_batch_donated = jax.jit(_solve_batch_impl, static_argnames=_STATIC,
                               donate_argnums=(0, 1))


def compile_cache_sizes() -> dict[str, int | None]:
    """Compiled program variants per primal entry point (mirrors
    ``mcf.compile_cache_sizes``; ``None`` = introspection unavailable)."""
    return {"solve": jit_cache_size(_solve),
            "solve_batch": jit_cache_size(_solve_batch,
                                          _solve_batch_donated)}


def solve_primal(cap: Topology | np.ndarray, dem: np.ndarray, *,
                 iters: int = 800, lr: float = 0.08, tol: float = 0.0,
                 check_every: int = 25, use_pallas: bool = False,
                 interpret: bool | None = None,
                 backend: str | None = None, aot=None,
                 d_max: int | None = None,
                 max_rounds: int | None = None) -> PrimalResult:
    """Certified lower bound on max-concurrent-flow throughput from an
    explicit feasible flow (plus the driving dual descent's upper bound —
    see module docstring).  ``cap``: a ``Topology`` or symmetric [N, N]
    capacity matrix; ``dem``: [N, N] demand — both in base line-speed
    units, so the (lb, ub) bracket is around the paper's dimensionless
    per-unit-demand θ*.  ``tol > 0`` stops early once the bracket gap's
    shrinkage per ``check_every``-step window drops below it.  ``backend``
    picks the APSP backend (``use_pallas=True`` aliases "squaring-pallas");
    ``aot`` is accepted for parity with the batch entry point and
    ignored."""
    del aot
    interpret = kops.resolve_interpret(interpret)
    cap_host = as_cap(cap)
    backend, d_max = resolve_backend_density(
        normalize_backend(backend, use_pallas), cap_host,
        n=cap_host.shape[0], d_max=d_max)
    capj = jnp.asarray(cap_host, jnp.float32)
    lb, ub, util, it = _solve(
        capj, jnp.asarray(dem, jnp.float32), jnp.int32(capj.shape[0]),
        jnp.float32(lr), jnp.float32(tol), iters=iters,
        check_every=check_every, backend=backend, interpret=interpret,
        d_max=d_max, max_rounds=max_rounds)
    return PrimalResult(float(lb), float(ub), float(util), int(it))


def solve_primal_batch(caps, dems, *, n_valid=None, iters: int = 800,
                       lr: float = 0.08, tol: float = 0.0,
                       check_every: int = 25, use_pallas: bool = False,
                       interpret: bool | None = None,
                       backend: str | None = None, aot=None,
                       sharding=None, donate: bool = False,
                       block: bool = True, d_max: int | None = None,
                       mean_degree: float | None = None,
                       max_rounds: int | None = None) -> PrimalBatchResult:
    """Batched primal solve over stacked [R, N, N] topologies/demands; the
    call surface mirrors ``mcf.solve_dual_batch`` exactly (``n_valid``
    padding masks, ``sharding``/``donate``/``block`` for the ``BatchPlan``
    async path), so primal lanes ride the same buckets/chunks/device
    sharding as dual lanes.  ``backend``/``aot`` mirror the dual too
    (APSP backend registry; persistent AOT compile cache)."""
    interpret = kops.resolve_interpret(interpret)
    backend = normalize_backend(backend, use_pallas)
    if len(caps) != len(dems):
        raise ValueError(f"caps ({len(caps)}) and dems ({len(dems)}) "
                         "must have equal length")
    if len(caps) == 0:
        z = np.zeros(0, np.float32)
        return PrimalBatchResult(z, z.copy(), z.copy(),
                                 np.zeros(0, np.int32))
    if not isinstance(caps, (np.ndarray, jax.Array)):
        caps = np.stack([as_cap(c) for c in caps])
    if not isinstance(dems, (np.ndarray, jax.Array)):
        dems = np.stack([np.asarray(d) for d in dems])
    if n_valid is None:
        n_valid = np.full(caps.shape[0], caps.shape[1], np.int32)
    backend, d_max = resolve_backend_density(
        backend, caps, n=caps.shape[1], d_max=d_max,
        mean_degree=mean_degree)
    capj = jnp.asarray(caps, jnp.float32)
    demj = jnp.asarray(dems, jnp.float32)
    nvj = jnp.asarray(n_valid, jnp.int32)
    if sharding is not None:
        capj, demj, nvj = jax.device_put((capj, demj, nvj), sharding)
    fn = _solve_batch_donated if donate else _solve_batch
    args = (capj, demj, nvj, jnp.float32(lr), jnp.float32(tol))
    static_kw = dict(iters=iters, check_every=check_every,
                     backend=backend, interpret=interpret,
                     d_max=d_max, max_rounds=max_rounds)
    lb, ub, util, it = aotcache.dispatch(
        fn, ("primal", "donated" if donate else "plain"), args, static_kw,
        aot=aot, sharding=sharding)
    if not block:
        return PrimalBatchResult(lb, ub, util, it)
    return PrimalBatchResult(np.asarray(lb), np.asarray(ub),
                             np.asarray(util), np.asarray(it))

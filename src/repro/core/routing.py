"""Routing-restricted throughput: ECMP and k-shortest-path lower bounds.

Every other engine in the repo scores a topology by ideal max-concurrent
flow — the fluid optimum any routing could at best achieve.  Real fabrics
route over restricted path sets, and the gap matters: Jellyfish (arXiv
1110.1687) made exactly this point by evaluating random graphs under
k-shortest-path routing with multipath transport, where ECMP's
shortest-only splitting strands a large fraction of the fluid capacity.
This module scores that deployable throughput as two certified LOWER
bounds on θ*, both driven by the same converged (min,+) APSP machinery
as the ideal solvers:

* **ECMP** (``solve_ecmp_batch``): split every demand equally over its
  equal-cost next hops — the SP-DAG membership test
  ``dist[v, t] == 1 + dist[u, t]`` on unit-hop APSP distances.  The
  split is a *linear* operator that strictly decreases distance-to-go,
  so one ``hops``-step fixed-point evaluation (no descent) yields the
  exact ECMP loads; ``1 / max_utilization`` is then a certified lower
  bound carried by an explicit feasible routing.
* **KSP** (``solve_ksp_batch``): restrict each pair to its k shortest
  simple paths (``repro.kernels.paths``, a static ``[pairs, k,
  max_hops + 1]`` tensor enumerated host-side at pack time) and optimise
  the per-pair split with multiplicative weights — softmax logits per
  (pair, path), Adam on a smoothed max-utilization (temperature-scaled
  logsumexp), the same cosine-decayed Adam + ``check_every``/``tol``
  early-stop + ``n_valid`` masking discipline as ``mcf.solve_dual_batch``.
  Every iterate's *exact* (unsmoothed) utilization certifies
  ``1 / umax``, so the running best is always a true lower bound.

**The ordering lattice.**  Both solvers also run the dual descent
(``mcf._descend``) in the same fused program, so every result carries
the ideal upper bound for free and the engines report
``meta["ideal_gap_pct"]`` — the certified price of the routing
restriction.  The KSP program additionally evaluates the ECMP operating
point (sharing its unit-hop APSP) and floors its bound with it: a
k-path multipath deployment never reports below the equal-split
baseline it deviates from.  That makes the bound ordering

    ``ecmp  <=  ksp(k)  <=  theta_exact  <=  dual ub``

mechanical on every instance — each step certified, none statistical.
(Jellyfish's measurement is the strict version of the first
inequality: KSP with enough paths recovers most of what ECMP leaves
behind.)  ``tests/test_conformance.py`` pins the full lattice across
all traffic patterns x graph families, and monotonicity in k against a
scipy ``linprog`` path-LP cross-check (``path_lp_throughput``).

Batching, padding, donation, sharding and AOT mirror ``primal``/``mcf``
exactly, so ``get_engine("ecmp")`` / ``get_engine("ksp")`` run whole
sweep families through ONE ``BatchPlan.execute`` with ``refill`` reuse
(``solver="ecmp"`` / ``"ksp"`` in ``plan.SOLVERS``).
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
from repro.core.mcf import (_INF, _descend, apsp, jit_cache_size,
                            resolve_backend_density)
from repro.kernels import ops as kops
from repro.kernels import paths as kpaths

__all__ = ["RoutingResult", "RoutingBatchResult", "solve_ecmp",
           "solve_ecmp_batch", "solve_ksp", "solve_ksp_batch",
           "path_lp_throughput", "compile_cache_sizes",
           "DEFAULT_K", "DEFAULT_MAX_HOPS"]

DEFAULT_K = 8          # path-set width: Jellyfish's evaluation sweet spot
DEFAULT_MAX_HOPS = 12  # per-path hop budget for the static path tensor
_MW_BETA = 32.0        # logsumexp sharpness of the smoothed max-utilization


@dataclasses.dataclass(frozen=True)
class RoutingResult:
    """One instance's routing-restricted solve: a certified LOWER bound
    on θ* under the routing restriction (an explicit feasible routing
    achieves it) plus the ideal dual descent's free UPPER bound, whose
    ratio is the certified price of the restriction."""

    throughput_lb: float      # certified routed lower bound
    throughput_ub: float      # ideal dual bound from the fused descent
    final_util: float         # max edge utilization of the final routing
    iterations: int           # optimisation steps executed (0 for pure ECMP)

    @property
    def gap(self) -> float:
        """Relative ideal-vs-routed gap (ub - lb) / ub."""
        return (self.throughput_ub - self.throughput_lb) / \
            max(self.throughput_ub, 1e-30)


@dataclasses.dataclass(frozen=True)
class RoutingBatchResult:
    """Per-instance outputs of one batched routing solve.  Indexing and
    iteration yield the certified lower bounds (``throughput_lb``); a
    ``block=False`` solve carries in-flight ``jax.Array``s (sync with
    ``jax.block_until_ready``)."""

    throughput_lb: np.ndarray   # [B] certified routed lower bound
    throughput_ub: np.ndarray   # [B] ideal dual bound (free)
    final_util: np.ndarray      # [B] max utilization of the final routing
    iterations: np.ndarray      # [B] optimisation steps per instance

    def __len__(self) -> int:
        return len(self.throughput_lb)

    def __getitem__(self, i):
        return self.throughput_lb[i]

    def __iter__(self):
        return iter(self.throughput_lb)


def _masked(cap, dem, n_valid):
    nmax = cap.shape[0]
    node_mask = jnp.arange(nmax) < n_valid
    pair_mask = node_mask[:, None] & node_mask[None, :]
    cap = jnp.where(pair_mask, cap, 0.0)
    dem = jnp.where(pair_mask, dem, 0.0)
    edge_mask = (cap > 0) & pair_mask
    safe_cap = jnp.where(edge_mask, cap, 1.0)
    return cap, dem, edge_mask, safe_cap


def _ecmp_eval(dem, edge_mask, safe_cap, *, backend, interpret, d_max,
               max_rounds, hops):
    """Exact ECMP loads via the fixed point of the equal-split operator.

    ``split[v, u, t]`` sends an equal share of v's t-bound traffic to
    every neighbour u one hop closer to t (SP-DAG membership on unit-hop
    distances; exact small integers, so the 0.5 tolerance is exact).
    The operator strictly decreases distance-to-go, so ``hops`` >=
    diameter applications of ``inflow = dem + inflow @ split`` reach the
    fixed point; the loads it induces are an explicit feasible routing
    of the full demand and ``1 / umax`` is certified.
    """
    nmax = edge_mask.shape[0]
    eye = jnp.eye(nmax, dtype=bool)
    w = jnp.where(edge_mask, 1.0, _INF)
    w = jnp.where(eye, 0.0, w)
    dist = apsp(w, backend, interpret, d_max, max_rounds)
    reach = dist < _INF / 2
    routable = ~jnp.any((dem > 0) & ~reach)
    nh = edge_mask[:, :, None] & reach[:, None, :] & \
        (jnp.abs(dist[:, None, :] - 1.0 - dist[None, :, :]) < 0.5)
    cnt = nh.sum(axis=1)                                   # [v, t]
    split = jnp.where(nh, 1.0 / jnp.maximum(cnt, 1)[:, None, :], 0.0)

    def body(_, inflow):
        return dem + jnp.einsum("vt,vut->ut", inflow, split)

    inflow = jax.lax.fori_loop(0, hops, body, dem)
    loads = jnp.einsum("vt,vut->vu", inflow, split)
    util = jnp.max(jnp.where(edge_mask, loads / safe_cap, 0.0))
    lb = jnp.where(routable & (util > 0),
                   1.0 / jnp.maximum(util, 1e-30), 0.0)
    return lb, util


def _ideal_ub(cap, dem, n_valid, lr_peak, tol, *, iters, check_every,
              backend, interpret, d_max, max_rounds):
    """Ideal dual upper bound from the shared descent (free bracket)."""
    best, it, z, dem_m, loss_of = _descend(
        cap, dem, n_valid, lr_peak, tol, iters=iters,
        check_every=check_every, backend=backend, interpret=interpret,
        d_max=d_max, max_rounds=max_rounds)
    _, final_ratio = loss_of(z, dem_m)
    return jnp.minimum(best, final_ratio), it


def _ecmp_one(cap, dem, n_valid, lr_peak, tol, *, iters, check_every,
              backend, interpret, d_max=None, max_rounds=None, hops):
    """One (possibly padded) instance: (ecmp lb, ideal ub, util, iters)."""
    capm, demm, edge_mask, safe_cap = _masked(cap, dem, n_valid)
    lb, util = _ecmp_eval(demm, edge_mask, safe_cap, backend=backend,
                          interpret=interpret, d_max=d_max,
                          max_rounds=max_rounds, hops=hops)
    ub, it = _ideal_ub(cap, dem, n_valid, lr_peak, tol, iters=iters,
                       check_every=check_every, backend=backend,
                       interpret=interpret, d_max=d_max,
                       max_rounds=max_rounds)
    return lb, ub, util, it


def _ksp_one(cap, dem, n_valid, paths, lr_peak, tol, *, iters,
             check_every, backend, interpret, d_max=None, max_rounds=None,
             hops):
    """One (possibly padded) instance of the k-path multiplicative-weights
    program: (ksp lb floored by ecmp, ideal ub, final util, MW iters).

    ``paths``: int32 ``[nmax * nmax, k, max_hops + 1]`` from
    ``repro.kernels.paths`` (-1 padded).  Certification: every iterate's
    exact utilization bounds a true feasible routing, and the ECMP
    evaluation shares this program's masks, so ``lb >= ecmp`` holds by
    construction (the documented lattice direction).
    """
    nmax = cap.shape[0]
    capm, demm, edge_mask, safe_cap = _masked(cap, dem, n_valid)
    ecmp_lb, _ = _ecmp_eval(demm, edge_mask, safe_cap, backend=backend,
                            interpret=interpret, d_max=d_max,
                            max_rounds=max_rounds, hops=hops)
    ub, _ = _ideal_ub(cap, dem, n_valid, lr_peak, tol, iters=iters,
                      check_every=check_every, backend=backend,
                      interpret=interpret, d_max=d_max,
                      max_rounds=max_rounds)

    a = paths[:, :, :-1]
    b = paths[:, :, 1:]
    hop_ok = (a >= 0) & (b >= 0)
    eidx = jnp.clip(a, 0) * nmax + jnp.clip(b, 0)          # [P, K, H]
    valid = paths[:, :, 0] >= 0                            # [P, K]
    demv = demm.reshape(-1)                                # [P]
    covered = jnp.any(valid, axis=1)
    routable = ~jnp.any((demv > 0) & ~covered)
    emask_f = edge_mask.reshape(-1)
    scap_f = safe_cap.reshape(-1)

    def util_of(logits):
        x = jax.nn.softmax(jnp.where(valid, logits, -1e9), axis=1)
        wgt = jnp.where(valid, x, 0.0) * demv[:, None]     # [P, K]
        contrib = jnp.where(hop_ok, wgt[:, :, None], 0.0)
        loads = jnp.zeros(nmax * nmax, jnp.float32).at[eidx].add(contrib)
        u = jnp.where(emask_f, loads / scap_f, 0.0)
        umax = jnp.max(u)
        # smooth surrogate: temperature-scaled logsumexp whose scale
        # tracks the (stop-gradient) current max, so the gradient always
        # resolves ties among near-tight edges at the same resolution
        s = jax.lax.stop_gradient(jnp.maximum(umax, 1e-30))
        soft = s / _MW_BETA * jax.nn.logsumexp(
            jnp.where(emask_f, u, -jnp.inf) * (_MW_BETA / s))
        return soft, umax

    grad_fn = jax.value_and_grad(util_of, has_aux=True)

    def lb_of(umax):
        return jnp.where(umax > 0, 1.0 / jnp.maximum(umax, 1e-30), 0.0)

    def cond(state):
        i = state[0]
        done = state[-1]
        return (i < iters) & ~done

    def step(state):
        i, logits, m, v, best, ref_best, _ = state
        (_, umax), g = grad_fn(logits)
        best = jnp.maximum(best, lb_of(umax))
        # Adam with cosine-decayed lr (mirrors the dual descent)
        t = i + 1
        lr = lr_peak * 0.5 * (1 + jnp.cos(jnp.pi * i / iters)) + 1e-3
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** t)
        vh = v / (1 - 0.999 ** t)
        logits = logits - lr * mh / (jnp.sqrt(vh) + 1e-8)
        at_check = t % check_every == 0
        rel_gain = (best - ref_best) / jnp.maximum(best, 1e-30)
        done = at_check & (rel_gain < tol)
        ref_best = jnp.where(at_check, best, ref_best)
        return t, logits, m, v, best, ref_best, done

    z0 = jnp.zeros(valid.shape, jnp.float32)   # uniform split at step 0
    init = (jnp.int32(0), z0, jnp.zeros_like(z0), jnp.zeros_like(z0),
            jnp.float32(0.0), jnp.float32(0.0), jnp.bool_(False))
    it, logits, _, _, best, _, _ = jax.lax.while_loop(cond, step, init)
    _, final_umax = util_of(logits)
    best = jnp.maximum(best, lb_of(final_umax))
    mw_lb = jnp.where(routable, best, 0.0)
    lb = jnp.maximum(mw_lb, ecmp_lb)           # the ECMP floor
    return lb, ub, final_umax, it


# compile-key statics: the dual/primal set plus the ECMP propagation
# depth (``hops``), which is resolved from the padded width only so
# every chunk of a bucket — and every ``refill`` round — shares keys
_STATIC = ("iters", "check_every", "backend", "interpret", "d_max",
           "max_rounds", "hops")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _ecmp(cap, dem, n_valid, lr_peak, tol, *, iters, check_every,
          backend, interpret, d_max=None, max_rounds=None, hops=None):
    return _ecmp_one(cap, dem, n_valid, lr_peak, tol, iters=iters,
                     check_every=check_every, backend=backend,
                     interpret=interpret, d_max=d_max,
                     max_rounds=max_rounds, hops=hops)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _ksp(cap, dem, n_valid, paths, lr_peak, tol, *, iters, check_every,
         backend, interpret, d_max=None, max_rounds=None, hops=None):
    return _ksp_one(cap, dem, n_valid, paths, lr_peak, tol, iters=iters,
                    check_every=check_every, backend=backend,
                    interpret=interpret, d_max=d_max,
                    max_rounds=max_rounds, hops=hops)


def _ecmp_batch_impl(caps, dems, n_valid, lr_peak, tol, *, iters,
                     check_every, backend, interpret, d_max=None,
                     max_rounds=None, hops=None):
    fn = functools.partial(_ecmp_one, iters=iters, check_every=check_every,
                           backend=backend, interpret=interpret,
                           d_max=d_max, max_rounds=max_rounds, hops=hops)
    return jax.vmap(fn, in_axes=(0, 0, 0, None, None))(
        caps, dems, n_valid, lr_peak, tol)


def _ksp_batch_impl(caps, dems, n_valid, paths, lr_peak, tol, *, iters,
                    check_every, backend, interpret, d_max=None,
                    max_rounds=None, hops=None):
    fn = functools.partial(_ksp_one, iters=iters, check_every=check_every,
                           backend=backend, interpret=interpret,
                           d_max=d_max, max_rounds=max_rounds, hops=hops)
    return jax.vmap(fn, in_axes=(0, 0, 0, 0, None, None))(
        caps, dems, n_valid, paths, lr_peak, tol)


_ecmp_batch = jax.jit(_ecmp_batch_impl, static_argnames=_STATIC)
_ecmp_batch_donated = jax.jit(_ecmp_batch_impl, static_argnames=_STATIC,
                              donate_argnums=(0, 1))
_ksp_batch = jax.jit(_ksp_batch_impl, static_argnames=_STATIC)
_ksp_batch_donated = jax.jit(_ksp_batch_impl, static_argnames=_STATIC,
                             donate_argnums=(0, 1))


def compile_cache_sizes() -> dict[str, int | None]:
    """Compiled program variants per routing entry point (mirrors
    ``mcf.compile_cache_sizes``; ``None`` = introspection unavailable)."""
    return {"ecmp": jit_cache_size(_ecmp),
            "ecmp_batch": jit_cache_size(_ecmp_batch, _ecmp_batch_donated),
            "ksp": jit_cache_size(_ksp),
            "ksp_batch": jit_cache_size(_ksp_batch, _ksp_batch_donated)}


def _resolve_hops(nmax: int, hops: int | None) -> int:
    # depth of the ECMP fixed-point loop; nmax always covers the
    # diameter, and depending only on the padded width keeps compile
    # keys shared across a bucket's chunks and refill rounds
    return int(hops) if hops is not None else int(nmax)


def _resolve_max_hops(nmax: int, max_hops: int | None) -> int:
    return int(max_hops) if max_hops is not None \
        else min(int(nmax) - 1, DEFAULT_MAX_HOPS)


def _paths_tensor(caps: np.ndarray, n_valid: np.ndarray, k: int,
                  max_hops: int) -> np.ndarray:
    """Host-side per-lane path enumeration, deduped across identical
    lanes (plan padding replicates instance 0 into surplus lanes, so
    those are free).  Capacity beyond each lane's ``n_valid`` is zeroed
    first, so no path ever visits a padded node."""
    caps = np.asarray(caps)
    r, nmax = caps.shape[0], caps.shape[1]
    node_ok = np.arange(nmax)[None, :] < np.asarray(n_valid)[:, None]
    masked = np.where(node_ok[:, :, None] & node_ok[:, None, :], caps, 0.0)
    out = np.empty((r, nmax * nmax, k, max_hops + 1), np.int32)
    cache: dict[bytes, np.ndarray] = {}
    for i in range(r):
        key = masked[i].tobytes()
        hit = cache.get(key)
        if hit is None:
            hit = kpaths.k_shortest_paths(masked[i], k, max_hops)
            hit = hit.reshape(nmax * nmax, k, max_hops + 1)
            cache[key] = hit
        out[i] = hit
    return out


def solve_ecmp(cap: Topology | np.ndarray, dem: np.ndarray, *,
               iters: int = 800, lr: float = 0.08, tol: float = 0.0,
               check_every: int = 25, use_pallas: bool = False,
               interpret: bool | None = None, backend: str | None = None,
               aot=None, d_max: int | None = None,
               max_rounds: int | None = None,
               hops: int | None = None) -> RoutingResult:
    """Certified ECMP lower bound for one instance (module docstring);
    the ideal dual upper bound rides along from the fused descent.
    ``hops`` caps the fixed-point propagation depth (default: N, always
    enough); the descent knobs only steer the free upper bound."""
    del aot
    interpret = kops.resolve_interpret(interpret)
    cap_host = as_cap(cap)
    n = cap_host.shape[0]
    backend, d_max = resolve_backend_density(
        normalize_backend(backend, use_pallas), cap_host, n=n, d_max=d_max)
    lb, ub, util, it = _ecmp(
        jnp.asarray(cap_host, jnp.float32), jnp.asarray(dem, jnp.float32),
        jnp.int32(n), jnp.float32(lr), jnp.float32(tol), iters=iters,
        check_every=check_every, backend=backend, interpret=interpret,
        d_max=d_max, max_rounds=max_rounds, hops=_resolve_hops(n, hops))
    return RoutingResult(float(lb), float(ub), float(util), int(it))


def solve_ksp(cap: Topology | np.ndarray, dem: np.ndarray, *,
              k: int = DEFAULT_K, max_hops: int | None = None,
              iters: int = 800, lr: float = 0.08, tol: float = 0.0,
              check_every: int = 25, use_pallas: bool = False,
              interpret: bool | None = None, backend: str | None = None,
              aot=None, d_max: int | None = None,
              max_rounds: int | None = None,
              hops: int | None = None) -> RoutingResult:
    """Certified k-shortest-path lower bound for one instance (module
    docstring): multiplicative weights over the k-path set, floored by
    the ECMP baseline, with the ideal dual upper bound riding along."""
    del aot
    interpret = kops.resolve_interpret(interpret)
    cap_host = as_cap(cap)
    n = cap_host.shape[0]
    backend, d_max = resolve_backend_density(
        normalize_backend(backend, use_pallas), cap_host, n=n, d_max=d_max)
    mh = _resolve_max_hops(n, max_hops)
    paths = _paths_tensor(cap_host[None], np.full(1, n, np.int32), k, mh)[0]
    lb, ub, util, it = _ksp(
        jnp.asarray(cap_host, jnp.float32), jnp.asarray(dem, jnp.float32),
        jnp.int32(n), jnp.asarray(paths), jnp.float32(lr),
        jnp.float32(tol), iters=iters, check_every=check_every,
        backend=backend, interpret=interpret, d_max=d_max,
        max_rounds=max_rounds, hops=_resolve_hops(n, hops))
    return RoutingResult(float(lb), float(ub), float(util), int(it))


def _prep_batch(caps, dems, n_valid, backend, use_pallas, d_max,
                mean_degree):
    if len(caps) != len(dems):
        raise ValueError(f"caps ({len(caps)}) and dems ({len(dems)}) "
                         "must have equal length")
    if not isinstance(caps, (np.ndarray, jax.Array)):
        caps = np.stack([as_cap(c) for c in caps])
    if not isinstance(dems, (np.ndarray, jax.Array)):
        dems = np.stack([np.asarray(d) for d in dems])
    if n_valid is None:
        n_valid = np.full(caps.shape[0], caps.shape[1], np.int32)
    backend, d_max = resolve_backend_density(
        normalize_backend(backend, use_pallas), caps, n=caps.shape[1],
        d_max=d_max, mean_degree=mean_degree)
    return caps, dems, np.asarray(n_valid, np.int32), backend, d_max


def _empty_batch() -> RoutingBatchResult:
    z = np.zeros(0, np.float32)
    return RoutingBatchResult(z, z.copy(), z.copy(), np.zeros(0, np.int32))


def solve_ecmp_batch(caps, dems, *, n_valid=None, iters: int = 800,
                     lr: float = 0.08, tol: float = 0.0,
                     check_every: int = 25, use_pallas: bool = False,
                     interpret: bool | None = None,
                     backend: str | None = None, aot=None, sharding=None,
                     donate: bool = False, block: bool = True,
                     d_max: int | None = None,
                     mean_degree: float | None = None,
                     max_rounds: int | None = None,
                     hops: int | None = None) -> RoutingBatchResult:
    """Batched ECMP solve over stacked [R, N, N] topologies/demands; the
    call surface mirrors ``mcf.solve_dual_batch`` exactly (``n_valid``
    padding masks, ``sharding``/``donate``/``block`` for the
    ``BatchPlan`` async path, ``aot`` persistent compile cache)."""
    interpret = kops.resolve_interpret(interpret)
    if len(caps) == 0:
        return _empty_batch()
    caps, dems, n_valid, backend, d_max = _prep_batch(
        caps, dems, n_valid, backend, use_pallas, d_max, mean_degree)
    capj = jnp.asarray(caps, jnp.float32)
    demj = jnp.asarray(dems, jnp.float32)
    nvj = jnp.asarray(n_valid, jnp.int32)
    if sharding is not None:
        capj, demj, nvj = jax.device_put((capj, demj, nvj), sharding)
    fn = _ecmp_batch_donated if donate else _ecmp_batch
    args = (capj, demj, nvj, jnp.float32(lr), jnp.float32(tol))
    static_kw = dict(iters=iters, check_every=check_every, backend=backend,
                     interpret=interpret, d_max=d_max,
                     max_rounds=max_rounds,
                     hops=_resolve_hops(caps.shape[1], hops))
    lb, ub, util, it = aotcache.dispatch(
        fn, ("ecmp", "donated" if donate else "plain"), args, static_kw,
        aot=aot, sharding=sharding)
    if not block:
        return RoutingBatchResult(lb, ub, util, it)
    return RoutingBatchResult(np.asarray(lb), np.asarray(ub),
                              np.asarray(util), np.asarray(it))


def solve_ksp_batch(caps, dems, *, n_valid=None, k: int = DEFAULT_K,
                    max_hops: int | None = None, iters: int = 800,
                    lr: float = 0.08, tol: float = 0.0,
                    check_every: int = 25, use_pallas: bool = False,
                    interpret: bool | None = None,
                    backend: str | None = None, aot=None, sharding=None,
                    donate: bool = False, block: bool = True,
                    d_max: int | None = None,
                    mean_degree: float | None = None,
                    max_rounds: int | None = None,
                    hops: int | None = None) -> RoutingBatchResult:
    """Batched KSP solve; surface = ``solve_ecmp_batch`` plus the path
    knobs ``k`` (paths per pair) and ``max_hops`` (per-path hop budget,
    default min(N - 1, DEFAULT_MAX_HOPS) — resolved from the padded
    width only, so refill rounds share compile keys).  Path tensors are
    enumerated host-side per lane (deduped across identical lanes)."""
    interpret = kops.resolve_interpret(interpret)
    if len(caps) == 0:
        return _empty_batch()
    caps, dems, n_valid, backend, d_max = _prep_batch(
        caps, dems, n_valid, backend, use_pallas, d_max, mean_degree)
    mh = _resolve_max_hops(caps.shape[1], max_hops)
    paths = _paths_tensor(np.asarray(caps), n_valid, k, mh)
    capj = jnp.asarray(caps, jnp.float32)
    demj = jnp.asarray(dems, jnp.float32)
    nvj = jnp.asarray(n_valid, jnp.int32)
    pj = jnp.asarray(paths)
    if sharding is not None:
        capj, demj, nvj, pj = jax.device_put((capj, demj, nvj, pj),
                                             sharding)
    fn = _ksp_batch_donated if donate else _ksp_batch
    args = (capj, demj, nvj, pj, jnp.float32(lr), jnp.float32(tol))
    static_kw = dict(iters=iters, check_every=check_every, backend=backend,
                     interpret=interpret, d_max=d_max,
                     max_rounds=max_rounds,
                     hops=_resolve_hops(caps.shape[1], hops))
    lb, ub, util, it = aotcache.dispatch(
        fn, ("ksp", "donated" if donate else "plain"), args, static_kw,
        aot=aot, sharding=sharding)
    if not block:
        return RoutingBatchResult(lb, ub, util, it)
    return RoutingBatchResult(np.asarray(lb), np.asarray(ub),
                              np.asarray(util), np.asarray(it))


def path_lp_throughput(cap: Topology | np.ndarray, dem: np.ndarray,
                       paths: np.ndarray) -> float:
    """Exact path-restricted max concurrent flow via scipy ``linprog``
    (HiGHS) — the small-instance cross-check for the MW solver.

    Variables are θ plus one flow per (demanded pair, valid path);
    conservation ties each pair's path flows to θ·dem, and every
    directed edge's summed load is capped.  ``paths`` is a
    ``[N, N, k, H + 1]`` or ``[N², k, H + 1]`` tensor from
    ``repro.kernels.paths``.  Returns 0.0 when any demanded pair has no
    path in the set (the restriction makes the demand unroutable).
    """
    from scipy.optimize import linprog

    cap = as_cap(cap)
    n = cap.shape[0]
    p = np.asarray(paths).reshape(n * n, *np.asarray(paths).shape[-2:])
    demv = np.asarray(dem, np.float64).reshape(-1)
    valid = p[:, :, 0] >= 0
    pairs = np.nonzero(demv > 0)[0]
    if len(pairs) == 0:
        return 0.0
    if not valid[pairs].any(axis=1).all():
        return 0.0
    ei, ej = np.nonzero(cap > 0)
    e_of = {(int(a), int(b)): r for r, (a, b) in enumerate(zip(ei, ej))}
    cols = [(pi, ki) for pi in pairs for ki in np.nonzero(valid[pi])[0]]
    nv = 1 + len(cols)
    a_ub = np.zeros((len(ei), nv))
    for c, (pi, ki) in enumerate(cols):
        seq = p[pi, ki]
        seq = seq[seq >= 0]
        for x, y in zip(seq[:-1], seq[1:]):
            a_ub[e_of[(int(x), int(y))], 1 + c] += 1.0
    a_eq = np.zeros((len(pairs), nv))
    for r, pi in enumerate(pairs):
        a_eq[r, 0] = -demv[pi]
        for c, (pj_, _) in enumerate(cols):
            if pj_ == pi:
                a_eq[r, 1 + c] = 1.0
    c_vec = np.zeros(nv)
    c_vec[0] = -1.0
    res = linprog(c_vec, A_ub=a_ub, b_ub=cap[ei, ej],
                  A_eq=a_eq, b_eq=np.zeros(len(pairs)),
                  bounds=[(0, None)] * nv, method="highs")
    if not res.success:
        raise RuntimeError(f"path LP failed: {res.message}")
    return float(res.x[0])

"""APSP backend registry (``ApspBackend``) and the shared SP-DAG
subgradient seam.

One public entry point, ``apsp(w, backend, interpret, d_max, max_rounds)``,
closes an (N, N) weight matrix over the tropical semiring.  The forward
pass dispatches on the backend registry:

* ``"squaring"``        — pure-jnp repeated (min,+) squaring (the legacy
  default path; ``O(N^3 log N)`` work, ``O(N^3)`` broadcast per step);
* ``"squaring-pallas"`` — repeated squaring on the Pallas tropical-matmul
  kernel (what ``use_pallas=True`` historically selected);
* ``"blocked-fw"``      — blocked Floyd-Warshall (``repro.kernels.fw``):
  one ``O(N^3)`` pass, ``O(N^2)`` live memory.  Compiled Pallas tiles on
  TPU (or with explicit ``interpret=True``); a ``lax.fori`` Floyd-Warshall
  on CPU where the interpreter would be the bottleneck;
* ``"ell-bf"``          — sparse-frontier Bellman-Ford relaxation over a
  fixed-width padded-ELL neighbor table (``repro.kernels.ell``).  The
  caller supplies the static table width ``d_max`` (>= the graph's max
  degree); work per round is ``O(N^2 d_max)`` and rounds stop at the
  diameter, so degree-bounded graphs close in a fraction of any dense
  pass.  Padded-ELL keeps every shape static: the backend jits, vmaps
  over solver lanes, and keys into the AOT cache like the dense ones;
* ``"auto"``            — ``"blocked-fw"`` for ``n >= AUTO_THRESHOLD``
  else ``"squaring"`` (a static shape decision, so it is jit-safe).
  When the caller can supply density information, ``resolve_backend``
  upgrades large sparse instances to ``"ell-bf"``: ``mean_degree <=
  SPARSE_THRESHOLD`` and ``n >= AUTO_THRESHOLD``.  A bare ``apsp(w,
  "auto")`` never goes sparse implicitly — density is a host-side fact
  the solvers compute from capacity patterns (``graphs.degree_stats``).

``normalize_backend`` maps the legacy ``use_pallas`` booleans threaded
through ``mcf``/``primal``/``engine`` onto registry names, so existing
call sites (``get_engine("dual-pallas")``, ``use_pallas=True``) keep
working unchanged.

**The subgradient seam.**  All backends share ONE ``jax.custom_vjp``
backward: a Bellman fixed-point adjoint that only needs the saved
``(w, D)`` pair.  At the fixed point ``D[s,t] = min_{k != t} D[s,k] +
w[k,t]`` (the diagonal is excluded so no cotangent leaks into the fixed
zero diagonal), so the backward peels one hop off the end of every
shortest path per sweep: the tie-split predecessor mask (relative
tolerance from PR 4) routes each pair's cotangent one edge back along
the SP-DAG, depositing the edge's share of ``dw`` as it goes, until the
mass drains onto the diagonal (path complete).  Consequences:

* subgradients are **identical across backends by construction** — the
  backward never sees which forward produced ``D``.  The ``"ell-bf"``
  backend routes the same walk through ``_sp_dag_grad_ell``, which
  enumerates predecessors from the ELL table (``O(N^2 d_max)`` per
  sweep) instead of walking dense N-chunks — the tie masks, counts, and
  routed masses are the same quantities, element for element;
* both flavors sum the one-hop cotangent ``un[s, k]`` as one left fold
  in ascending target ``t``.  The dense flavor adds one target's slice
  at a time; the ELL flavor folds predecessor-major, one slot per
  predecessor per step, so a hop takes as many steps as the most slots
  any predecessor holds (the out-degree, on a symmetric fabric) rather
  than one per target.  Skipping a target that does not list ``k`` is
  exact: its term is ``+0.0`` or ``-0.0``, the accumulator starts at
  ``+0.0``, and round-to-nearest makes a sum ``-0.0`` only when both
  addends are, so the accumulator is never ``-0.0`` and adding a zero
  to it changes no bit;
* per-pair gradient mass is a unit flow routed on shortest paths (what
  the Frank-Wolfe primal oracle requires);
* backward memory is ``O(N^2 * chunk)`` (t-chunked mask slabs) instead
  of the ``O(N^3)`` tie-mask of the per-matmul VJP, and backward work is
  ``O(diameter * N^3 / chunk-parallelism)`` — diameters of the graphs
  here are small.  Chunks whose cotangent has fully drained (and padded
  lanes, which never carry mass) are skipped by a ``lax.cond`` instead
  of relaxing all-``_INF`` rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core.spans import scoped
from repro.kernels import ell as kell
from repro.kernels import fw as kfw
from repro.kernels import ops as kops

__all__ = ["apsp", "normalize_backend", "resolve_backend", "BACKENDS",
           "AUTO_THRESHOLD", "SPARSE_THRESHOLD", "_INF"]

_INF = 1.0e18   # non-edge sentinel: survives one add in float32 headroom

BACKENDS = ("squaring", "squaring-pallas", "blocked-fw", "ell-bf", "auto")
AUTO_THRESHOLD = 512     # auto: blocked-fw at and above this padded size
SPARSE_THRESHOLD = 32.0  # auto: ell-bf when mean degree is at most this
_FW_TILE = 128           # Pallas tile for the blocked-fw flavor
_BWD_ELEMS = 1 << 25     # float budget for one backward mask slab


def normalize_backend(backend: str | bool | None = None,
                      use_pallas: bool = False) -> str:
    """Map a backend spec (registry name, legacy ``use_pallas`` bool, or
    None) to a registry name.  ``None`` defers to ``use_pallas`` for
    compatibility: True -> "squaring-pallas", False -> "auto"."""
    if backend is None:
        return "squaring-pallas" if use_pallas else "auto"
    if isinstance(backend, bool):   # legacy positional use_pallas slot
        return "squaring-pallas" if backend else "squaring"
    if backend not in BACKENDS:
        raise ValueError(f"unknown APSP backend {backend!r}; "
                         f"known: {BACKENDS}")
    return backend


def resolve_backend(backend: str, n: int, *,
                    mean_degree: float | None = None) -> str:
    """Resolve "auto" against a concrete (static) matrix size, and — when
    the caller knows it — the graph's mean degree.  Density is optional
    and host-side: without it the choice is the dense PR 7 ladder; with
    it, large degree-bounded instances resolve to ``"ell-bf"``."""
    backend = normalize_backend(backend)
    if backend == "auto":
        if (mean_degree is not None and n >= AUTO_THRESHOLD
                and mean_degree <= SPARSE_THRESHOLD):
            return "ell-bf"
        return "blocked-fw" if n >= AUTO_THRESHOLD else "squaring"
    return backend


def _squaring_steps(n: int) -> int:
    return max(1, math.ceil(math.log2(max(n - 1, 2))))


def _clamp_d_max(d_max: int, n: int) -> int:
    return max(1, min(int(d_max), max(n - 1, 1)))


def _pack_ell(w: jax.Array, d_max: int) -> tuple[jax.Array, jax.Array]:
    """Pack a dense weight matrix into incoming padded-ELL tables: row
    ``t`` of ``(idx, wgt)`` lists the predecessors ``k`` with ``wgt[t, j]
    = w[idx[t, j], t]``, sorted ascending, pads LAST with ``idx = t`` /
    ``wgt = _INF`` (the convention ``repro.kernels.ell`` relaxes and
    ``Topology.to_ell`` exports).  Traceable, so the solvers can pack
    under jit/vmap; ``d_max`` must cover the max in-degree — rows with
    more finite entries than ``d_max`` would be silently truncated, so
    host layers validate it (``graphs.degree_stats``)."""
    n = w.shape[-1]
    d_max = _clamp_d_max(d_max, n)
    rows = jnp.arange(n)
    # wt[t, k] = w[k, t]; the diagonal is masked so the zero self-entry
    # never competes with real edges for a table slot
    wt = jnp.where(rows[:, None] == rows[None, :], _INF,
                   jnp.swapaxes(w, -1, -2).astype(jnp.float32))
    neg, cols = jax.lax.top_k(-wt, d_max)     # d_max smallest per row
    vals = -neg
    valid = vals < _INF / 2
    order = jnp.argsort(jnp.where(valid, cols, n), axis=-1)  # pads last
    idx = jnp.take_along_axis(jnp.where(valid, cols, rows[:, None]),
                              order, axis=-1).astype(jnp.int32)
    wgt = jnp.take_along_axis(jnp.where(valid, vals, _INF), order, axis=-1)
    return idx, wgt


@scoped("apsp_fwd")
def _apsp_forward(w: jax.Array, backend: str, interpret: bool | None,
                  d_max: int | None = None, max_rounds: int | None = None):
    n = w.shape[0]
    kind = resolve_backend(backend, n)
    d = w.astype(jnp.float32)
    if kind == "ell-bf":
        if d_max is None:
            raise ValueError("ell-bf needs a static d_max (max degree of "
                             "the packed table); compute it host-side, "
                             "e.g. graphs.degree_stats(cap)")
        idx, wgt = _pack_ell(d, d_max)
        # same flavor split as blocked-fw below: the solvers pre-resolve
        # interpret=None to True on CPU, so only the platform can pick
        # the Pallas round here; tests drive it via kernels.ell directly
        dd, _ = kell.ell_bf_apsp_impl(
            idx, wgt, max_rounds=max_rounds,
            use_pallas=jax.default_backend() == "tpu", interpret=interpret)
        return dd
    if kind == "blocked-fw":
        # the tiled Pallas kernel only pays off compiled (TPU); elsewhere
        # the lax.fori Floyd-Warshall is the fast flavor (the solvers
        # pre-resolve interpret=None to True on CPU, so an interpret bool
        # cannot distinguish "explicitly requested interpreter" here —
        # tests drive the 4-phase interpret path via kernels.fw directly)
        if jax.default_backend() != "tpu":
            return kfw.fw_apsp_jnp(d)
        pad = (-n) % _FW_TILE
        if pad:
            d = jnp.pad(d, ((0, pad), (0, pad)), constant_values=_INF)
        d = kfw.fw_apsp_pallas(d, t=_FW_TILE, interpret=interpret)
        return d[:n, :n] if pad else d
    for _ in range(_squaring_steps(n)):
        if kind == "squaring-pallas":
            d = jnp.minimum(d, kops.minplus_matmul(d, d, 128, interpret))
        else:
            d = jnp.minimum(d, jnp.min(d[:, :, None] + d[None, :, :],
                                       axis=1))
    return d


def _bwd_chunk(n: int, d_max: int | None = None) -> int:
    per_target = n * (d_max if d_max is not None else n)
    return max(1, min(n, _BWD_ELEMS // max(per_target, 1)))


def _sp_dag_grad(w: jax.Array, d: jax.Array, g: jax.Array) -> jax.Array:
    """Backward of the APSP closure: route the cotangent ``g`` on ``D``
    back along the shortest-path DAG of ``(w, D)``, one hop per sweep."""
    n = w.shape[0]
    eye = jnp.eye(n, dtype=bool)
    reach = d < _INF / 2
    # no gradient through the fixed zero diagonal or unreachable pairs
    # (D is locally constant at the sentinel there)
    u0 = jnp.where(reach & ~eye, g, 0.0).astype(jnp.float32)
    c = _bwd_chunk(n)
    pad = (-n) % c
    wf = w.astype(jnp.float32)
    df = d.astype(jnp.float32)
    if pad:
        wf = jnp.pad(wf, ((0, pad), (0, pad)), constant_values=_INF)
        df = jnp.pad(df, ((0, pad), (0, pad)), constant_values=_INF)
        u0 = jnp.pad(u0, ((0, pad), (0, pad)))
    m = n + pad
    eye_m = jnp.eye(m, dtype=bool)
    kidx = jnp.arange(m)

    def one_hop(u, dw):
        def chunk_body(j, acc):
            t0 = j * c
            uc = jax.lax.dynamic_slice_in_dim(u, t0, c, axis=1)

            def relax(acc):
                un, dwn = acc
                wc = jax.lax.dynamic_slice_in_dim(wf, t0, c, axis=1)
                dc = jax.lax.dynamic_slice_in_dim(df, t0, c, axis=1)
                s = df[:, :, None] + wc[None, :, :]               # (m, m, c)
                # relative tie tolerance (PR 4): edge lengths span many
                # orders of magnitude under the dual's log-length ascent
                tol = 1e-6 * jnp.maximum(jnp.abs(dc), 1e-6)
                mask = s <= (dc + tol)[:, None, :]
                # k == t would tie via the zero diagonal every sweep and
                # stall the drain; the fixed point excludes it
                mask &= (kidx[None, :, None]
                         != (t0 + jnp.arange(c))[None, None, :])
                mf = mask.astype(jnp.float32)
                mf = mf / jnp.maximum(mf.sum(axis=1, keepdims=True), 1.0)
                mf = mf * uc[:, None, :]
                # cotangent one hop back, accumulated STRICTLY in
                # ascending-target order: left-to-right float addition is
                # chunking-invariant, which is what lets the ELL-aware
                # adjoint (different chunk widths) stay bit-identical
                un = jax.lax.fori_loop(
                    0, c,
                    lambda tc, acc: acc + jax.lax.dynamic_index_in_dim(
                        mf, tc, axis=2, keepdims=False),
                    un)
                dep = jax.lax.dynamic_slice_in_dim(dwn, t0, c, axis=1)
                dwn = jax.lax.dynamic_update_slice_in_dim(
                    dwn, dep + mf.sum(axis=0), t0, axis=1)
                return un, dwn

            # a drained chunk — and every fully-padded all-_INF chunk,
            # whose lanes can never carry mass — routes zeros; skip the
            # O(m^2 c) slab instead of relaxing it (exact: the slab with
            # uc == 0 adds +0.0 everywhere, so bits are unchanged)
            return jax.lax.cond(jnp.any(uc != 0.0), relax,
                                lambda acc: acc, acc)

        return jax.lax.fori_loop(0, m // c, chunk_body,
                                 (jnp.zeros_like(u), dw))

    def cond(carry):
        u, _, it = carry
        return (it < m) & (jnp.max(jnp.abs(u)) > 0.0)

    def body(carry):
        u, dw, it = carry
        u2, dw2 = one_hop(u, dw)
        # mass arriving on the diagonal is a completed path
        return jnp.where(eye_m, 0.0, u2), dw2, it + 1

    _, dw, _ = jax.lax.while_loop(cond, body,
                                  (u0, jnp.zeros_like(wf), 0))
    if pad:
        dw = dw[:n, :n]
    return dw.astype(w.dtype)


def _fold_plan(ic: jax.Array, wc: jax.Array, m: int):
    """Predecessor-major plan of one chunk's ELL slots ``(ic, wc)``, each
    ``(c, d_max)``: ``slots[i, k]`` is the flat index of the ``i``-th slot
    (ascending target) whose predecessor is ``k``, or -1 past the last;
    ``steps`` is the most slots any ``k`` holds.  Pad slots key past the
    last column and are never listed."""
    c = ic.shape[0]
    key = jnp.where(wc < _INF / 2, ic, m).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    cnt = jnp.zeros(m, jnp.int32).at[key].add(1, mode="drop")
    k = key[order]
    # a target lists k at most once, so a rank never reaches c
    rank = jnp.arange(k.shape[0]) - (jnp.cumsum(cnt) - cnt)[
        jnp.minimum(k, m - 1)]
    slots = jnp.full((c, m), -1, jnp.int32).at[rank, k].set(
        order, mode="drop")
    return slots, cnt.max()


def _sp_dag_grad_ell(w: jax.Array, d: jax.Array, g: jax.Array,
                     d_max: int) -> jax.Array:
    """ELL-aware flavor of :func:`_sp_dag_grad`: the one-hop walk
    enumerates each target's predecessors from the incoming ELL table
    (``d_max`` slots) instead of scanning all N candidates, so a sweep
    is ``O(N^2 d_max)`` work and the mask slab is ``(N, chunk, d_max)``.
    Same tie masks, same counts, same routed masses — the table rows ARE
    the finite column entries of ``w``, ascending, and pads carry
    ``_INF`` so they never tie."""
    n = w.shape[0]
    d_max = _clamp_d_max(d_max, n)
    idx, wgt = _pack_ell(w, d_max)      # idx[t, j] = k, wgt[t, j] = w[k, t]
    eye = jnp.eye(n, dtype=bool)
    reach = d < _INF / 2
    u0 = jnp.where(reach & ~eye, g, 0.0).astype(jnp.float32)
    df = d.astype(jnp.float32)
    c = _bwd_chunk(n, d_max)
    pad = (-n) % c
    if pad:
        # pad the TARGET axis only (predecessors stay the n real rows):
        # padded rows get idx 0 / wgt _INF, so they tie nowhere and the
        # fold never lists their slots
        idx = jnp.pad(idx, ((0, pad), (0, 0)))
        wgt = jnp.pad(wgt, ((0, pad), (0, 0)), constant_values=_INF)
        df_t = jnp.pad(df, ((0, 0), (0, pad)), constant_values=_INF)
        u0 = jnp.pad(u0, ((0, 0), (0, pad)))
    else:
        df_t = df
    m = n + pad
    diag = jnp.arange(n)[:, None] == jnp.arange(m)[None, :]
    # the fold's plan per chunk, fixed for the whole backward: slots in
    # predecessor-major order, ascending target within each predecessor
    slots, steps = jax.vmap(_fold_plan, in_axes=(0, 0, None))(
        idx.reshape(m // c, c, d_max), wgt.reshape(m // c, c, d_max), m)

    def one_hop(u, dw_ell):
        def chunk_body(j, acc):
            t0 = j * c
            uc = jax.lax.dynamic_slice_in_dim(u, t0, c, axis=1)

            def relax(acc):
                un_t, dwn = acc
                ic = jax.lax.dynamic_slice_in_dim(idx, t0, c, axis=0)
                wc = jax.lax.dynamic_slice_in_dim(wgt, t0, c, axis=0)
                dc = jax.lax.dynamic_slice_in_dim(df_t, t0, c, axis=1)
                # dk[s, tc, j] = D[s, idx[t0 + tc, j]]
                dk = jnp.take(df, ic.reshape(-1), axis=1,
                              ).reshape(n, c, d_max)
                s = dk + wc[None, :, :]                    # (n, c, d_max)
                tol = 1e-6 * jnp.maximum(jnp.abs(dc), 1e-6)
                # pads carry _INF and the diagonal is never packed, so
                # non-edges and k == t are excluded by construction
                mask = (s <= (dc + tol)[:, :, None]) & (wc < _INF / 2)[None]
                mf = mask.astype(jnp.float32)
                mf = mf / jnp.maximum(mf.sum(axis=2, keepdims=True), 1.0)
                mf = mf * uc[:, :, None]
                # cotangent one hop back, folded predecessor-major: step
                # i adds, for every k at once, the slab row of k's i-th
                # slot (ascending target), so each un[s, k] is the same
                # left fold in ascending t as a target-by-target scatter,
                # in max-slots-per-k steps instead of c
                rows = jnp.moveaxis(mf, 0, 2)              # (c, d_max, n)
                tab = slots[j]

                def fold(i, un_t):
                    slot = tab[i]
                    live = slot >= 0
                    slot = jnp.maximum(slot, 0)
                    row = rows[slot // d_max, slot % d_max]    # (m, n)
                    return un_t + jnp.where(live[:, None], row, 0.0)

                un_t = jax.lax.fori_loop(0, steps[j], fold, un_t)
                dep = jax.lax.dynamic_slice_in_dim(dwn, t0, c, axis=0)
                dwn = jax.lax.dynamic_update_slice_in_dim(
                    dwn, dep + mf.sum(axis=0), t0, axis=0)
                return un_t, dwn

            return jax.lax.cond(jnp.any(uc != 0.0), relax,
                                lambda acc: acc, acc)

        # the fold runs on un transposed, (k, s), so each step reads
        # whole slab rows
        un_t, dw_ell = jax.lax.fori_loop(
            0, m // c, chunk_body,
            (jnp.zeros((m, n), jnp.float32), dw_ell))
        return un_t.T, dw_ell

    def cond(carry):
        u, _, it = carry
        return (it < m) & (jnp.max(jnp.abs(u)) > 0.0)

    def body(carry):
        u, dw_ell, it = carry
        u2, dw2 = one_hop(u, dw_ell)
        return jnp.where(diag, 0.0, u2), dw2, it + 1

    _, dw_ell, _ = jax.lax.while_loop(
        cond, body, (u0, jnp.zeros((m, d_max), jnp.float32), 0))
    # deposits live in ELL layout dw_ell[t, j]; one scatter lands them on
    # the dense edge (k = idx[t, j], t).  Pads add +0.0 to the diagonal.
    dw = jnp.zeros((n, n), jnp.float32)
    dw = dw.at[idx[:n], jnp.arange(n)[:, None]].add(dw_ell[:n])
    return dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def apsp(w: jax.Array, backend: str = "auto",
         interpret: bool | None = None, d_max: int | None = None,
         max_rounds: int | None = None) -> jax.Array:
    """All-pairs shortest path lengths of a dense weighted digraph.

    ``w``: (N, N) edge lengths, zero diagonal, ``_INF`` for non-edges
    (positive lengths; zero-length cycles make the subgradient tie-split
    ill-defined).  ``backend`` is an ``ApspBackend`` registry name (see
    module docstring); ``interpret`` is the Pallas escape hatch threaded
    to the kernels.  ``d_max`` (static, required by ``"ell-bf"``) is the
    padded-ELL table width — at least the graph's max degree — and
    ``max_rounds`` (static, optional) caps the relaxation rounds, default
    N; both are compile-key material.  Differentiable on every backend
    via the shared fixed-point adjoint."""
    return _apsp_forward(w, normalize_backend(backend), interpret,
                         d_max, max_rounds)


def _apsp_fwd(w, backend, interpret, d_max, max_rounds):
    d = _apsp_forward(w, normalize_backend(backend), interpret,
                      d_max, max_rounds)
    return d, (w, d)


@scoped("apsp_bwd")
def _apsp_bwd(backend, interpret, d_max, max_rounds, res, g):
    w, d = res
    if resolve_backend(backend, w.shape[0]) == "ell-bf":
        return (_sp_dag_grad_ell(w, d, g, d_max),)
    return (_sp_dag_grad(w, d, g),)


apsp.defvjp(_apsp_fwd, _apsp_bwd)

"""ELL-packed Bellman-Ford APSP for degree-bounded graphs.

Dense APSP backends relax every (k, t) pair — on a degree-16 random
regular graph at N=8192 the weight matrix is >99% ``_INF`` sentinels and
both blocked Floyd-Warshall and repeated squaring burn nearly all their
work on non-edges.  This module packs the adjacency into a fixed-width
padded-ELL table — ``idx[N, d_max]`` int32 neighbor ids + ``wgt[N,
d_max]`` float32 lengths, pads at the END of each row with ``idx = own
row`` (a safe self-gather) and ``wgt = _INF`` — and closes it with
batched Bellman-Ford relaxation rounds.  Degree-bounded graphs make the
pad waste tiny and every shape static, so the kernel jits, vmaps over
solver lanes, and keys cleanly into the AOT compile cache.

**Table orientation.**  Row ``v`` lists the tails of edges INTO ``v``:
``idx[v, j] = u`` and ``wgt[v, j] = w(u -> v)``.  On the symmetric
capacity patterns the repo solves, in-neighbors equal out-neighbors and
only the weights are directional (``repro.core.apsp._pack_ell`` packs
the transpose for exactly this reason).

**The recurrence is row-pull, not column-push.**  The textbook update
``d[:, v] = min(d[:, v], min_u d[:, u] + w(u, v))`` gathers strided
COLUMNS of the distance carry — measured 25x slower than pulling whole
rows.  We carry the transpose ``m[t, s] = dist(s -> t)`` and relax a
tile of target rows at a time::

    m[t, :] = min(m[t, :], min_j wgt[t, j] + m[idx[t, j], :])

so every gather is ``d_max`` contiguous row reads.  Tiles are swept in
order within a round (Gauss-Seidel: later tiles see already-relaxed
rows), which only accelerates the monotone descent — the fixed point is
the exact shortest-path closure either way, reached in O(diameter)
rounds with a per-round convergence flag for early exit.

Flavors (mirroring ``repro.kernels.fw``):

* ``ell_bf_apsp`` — full (N, N) closure in one jitted program; what the
  ``"ell-bf"`` registry backend runs.  Off-TPU each round is the jnp
  tile sweep; on TPU (or with explicit ``interpret=True``) it is a
  Jacobi round as a Pallas grid over (target tile, source slab) blocks.
  The carry stays in HBM and each predecessor row slab is gathered by
  DMA, with the tile's slice of the ``idx``/``wgt`` tables blocked into
  SMEM, so neither VMEM nor SMEM use grows with N.  Same fixed point as
  the Gauss-Seidel sweep.
* ``ell_bf_apsp_streamed`` — the frontier path: host-streamed source
  blocks.  Each block's ``(N, S)`` transposed carry converges
  independently (its own early exit) and lands in one preallocated host
  array, so peak memory is ONE N^2 f32 output + O(N x S) device state —
  this is what moves the 1.5 GB frontier from N=4096 to N>=16384.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.minplus import resolve_interpret

__all__ = ["ell_bf_apsp", "ell_bf_apsp_streamed", "DEFAULT_TILE",
           "DEFAULT_BLOCK"]

_INF = 1.0e18      # == repro.core.apsp._INF (no circular import; test-pinned)
DEFAULT_TILE = 1024    # target rows per relaxation tile (CPU sweet spot)
DEFAULT_BLOCK = 1024   # source columns per streamed block
_SLAB = 1024           # sources per DMA'd row slab: one (8, 128) f32 tile
_GATHER_BYTES = 4 << 20   # VMEM for one Pallas tile's gathered row slabs


def _check_tables(idx: jax.Array, wgt: jax.Array) -> tuple[int, int]:
    if idx.ndim != 2 or idx.shape != wgt.shape:
        raise ValueError(f"ELL tables must be matching (N, d_max) arrays, "
                         f"got idx {idx.shape} / wgt {wgt.shape}")
    if not jnp.issubdtype(idx.dtype, jnp.integer):
        raise ValueError(f"ELL idx must be integer, got {idx.dtype}")
    return int(idx.shape[0]), int(idx.shape[1])


def _relax_tiles_jnp(m, idx, wgt, *, tile: int):
    """One Gauss-Seidel relaxation round over target tiles.  Returns
    (new carry, changed flag).  ``tile`` need not divide N: the trailing
    tile's dynamic slice clamps and overlaps already-relaxed rows, which
    re-applies an idempotent min — harmless to the fixed point."""
    n, d_max = idx.shape

    def relax_tile(ti, carry):
        m, changed = carry
        t0 = ti * tile
        mt = jax.lax.dynamic_slice_in_dim(m, t0, tile, axis=0)
        it = jax.lax.dynamic_slice_in_dim(idx, t0, tile, axis=0)
        wt = jax.lax.dynamic_slice_in_dim(wgt, t0, tile, axis=0)

        def slot(j, acc):
            # one contiguous row gather per ELL column: m[idx[t, j], :]
            return jnp.minimum(acc,
                               jnp.take(m, it[:, j], axis=0) + wt[:, j, None])

        new = jax.lax.fori_loop(0, d_max, slot, mt)
        changed = changed | jnp.any(new < mt)
        return jax.lax.dynamic_update_slice_in_dim(m, new, t0, axis=0), changed

    nt = -(-n // tile)
    return jax.lax.fori_loop(0, nt, relax_tile, (m, jnp.bool_(False)))


def _pallas_tile(d_max: int) -> int:
    """Target rows per Pallas grid step: the largest power of two in
    [8, 128] whose gathered row slabs (``d_max`` per row) fit
    ``_GATHER_BYTES`` of VMEM.  Independent of N."""
    tile = 128
    while tile > 8 and d_max * tile * _SLAB * 4 > _GATHER_BYTES:
        tile //= 2
    return tile


def _relax_round_kernel(idx_ref, wgt_ref, m_hbm, mt_ref, o_ref, buf, sem, *,
                        tile: int, d_max: int):
    """One (target tile, source slab) block of a Jacobi round.

    ``idx_ref``/``wgt_ref`` are this tile's rows of the flattened ELL
    tables, in SMEM; ``m_hbm`` is the whole pre-round carry, shaped (N,
    S/128, 128) and left in HBM; ``mt_ref`` is this tile's own rows.
    Every predecessor row slab ``m[idx[t, j], slab]`` — one (8, 128)
    tile — is DMA'd into ``buf[j, r]``, so VMEM holds ``d_max * tile``
    slabs whatever N is."""
    slab = pl.multiple_of(pl.program_id(1) * (_SLAB // 128), _SLAB // 128)

    def copy(r, j, k):
        return pltpu.make_async_copy(
            m_hbm.at[k, pl.ds(slab, _SLAB // 128)], buf.at[j, r], sem)

    def start(r, c):
        for j in range(d_max):
            copy(r, j, idx_ref[0, r * d_max + j]).start()
        return c

    def wait(r, c):
        # one semaphore counts every copy's bytes: a slab may be read only
        # once ALL copies have landed
        for j in range(d_max):
            copy(r, j, 0).wait()
        return c

    def relax(r, c):
        acc = mt_ref[r]
        for j in range(d_max):
            acc = jnp.minimum(acc, buf[j, r] + wgt_ref[0, r * d_max + j])
        o_ref[r] = acc
        return c

    jax.lax.fori_loop(0, tile, start, 0)
    jax.lax.fori_loop(0, tile, wait, 0)
    jax.lax.fori_loop(0, tile, relax, 0)


def _relax_round_call(m, idx, wgt, *, tile: int, interpret: bool | None):
    """One Jacobi round on an aligned carry: N a multiple of ``tile``,
    S a multiple of ``_SLAB``.  Every block reads the pre-round carry
    (the grid is unordered, so tiles never see each other's updates
    within a round)."""
    n, d_max = idx.shape
    s = m.shape[1]
    m3 = m.reshape(n, s // 128, 128)
    # (tiles, 1, tile * d_max): a block whose last two dims are whole
    # keeps Mosaic's alignment rule satisfied when vmap prepends lanes
    table = pl.BlockSpec((None, 1, tile * d_max), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    block = pl.BlockSpec((tile, _SLAB // 128, 128), lambda i, j: (i, j, 0))
    out = pl.pallas_call(
        functools.partial(_relax_round_kernel, tile=tile, d_max=d_max),
        grid=(n // tile, s // _SLAB),
        in_specs=[table, table, pl.BlockSpec(memory_space=pl.ANY), block],
        out_specs=block,
        scratch_shapes=[
            pltpu.VMEM((d_max, tile, _SLAB // 128, 128), jnp.float32),
            pltpu.SemaphoreType.DMA(())],
        out_shape=jax.ShapeDtypeStruct(m3.shape, jnp.float32),
        interpret=resolve_interpret(interpret))(
            idx.reshape(n // tile, 1, -1), wgt.reshape(n // tile, 1, -1),
            m3, m3)
    return out.reshape(n, s)


def _relax_round(m, idx, wgt, *, tile: int, interpret: bool | None):
    """``_relax_round_call``, batched lane by lane under vmap: Pallas
    batches by prepending a grid axis, and a block of the HBM carry
    (memory space ANY) cannot take one."""

    @jax.custom_batching.custom_vmap
    def round_(m, idx, wgt):
        return _relax_round_call(m, idx, wgt, tile=tile, interpret=interpret)

    @round_.def_vmap
    def _lanes(axis_size, in_batched, m, idx, wgt):
        lanes = [x if b else jnp.broadcast_to(x, (axis_size, *x.shape))
                 for x, b in zip((m, idx, wgt), in_batched)]
        return jax.lax.map(lambda a: round_(*a), lanes), True

    return round_(m, idx, wgt)


def _bf_fixpoint(idx, wgt, m0, *, tile: int, max_rounds: int,
                 use_pallas: bool, interpret: bool | None):
    """Relax a transposed carry ``m0[t, s]`` to the shortest-path fixed
    point.  Traceable (no jit/donation here) so ``repro.core.apsp`` can
    inline it under the solvers' jit/vmap.  Returns (m, rounds).

    The Pallas flavor pads the carry once to whole (``_pallas_tile``
    rows, ``_SLAB`` sources) blocks: padded targets list only themselves
    at ``_INF`` and padded sources start at ``_INF``, so neither ever
    changes nor reaches a real entry."""
    n, s = m0.shape
    m0 = m0.astype(jnp.float32)
    if use_pallas:
        d_max = idx.shape[1]
        ptile = _pallas_tile(d_max)
        pad_n, pad_s = (-n) % ptile, (-s) % _SLAB
        own = jnp.broadcast_to(jnp.arange(n, n + pad_n)[:, None],
                               (pad_n, d_max))
        idx = jnp.concatenate([idx.astype(jnp.int32),
                               own.astype(jnp.int32)])
        wgt = jnp.pad(wgt.astype(jnp.float32), ((0, pad_n), (0, 0)),
                      constant_values=_INF)
        m0 = jnp.pad(m0, ((0, pad_n), (0, pad_s)), constant_values=_INF)

    def round_(carry):
        m, _, rounds = carry
        if use_pallas:
            new = _relax_round(m, idx, wgt, tile=ptile, interpret=interpret)
            m, ch = new, jnp.any(new != m)
        else:
            m, ch = _relax_tiles_jnp(m, idx, wgt, tile=tile)
        return m, ch, rounds + 1

    def cond(carry):
        return carry[1] & (carry[2] < max_rounds)

    m, _, rounds = jax.lax.while_loop(
        cond, round_, (m0, jnp.bool_(True), jnp.int32(0)))
    return m[:n, :s], rounds


def _full_init(idx, wgt):
    """Transposed one-hop carry for ALL sources: m0[t, s] = w(s -> t),
    0 on the diagonal, _INF elsewhere.  Row t of the (incoming) tables
    scatters exactly the w(s -> t) entries; pads self-scatter _INF."""
    n = idx.shape[0]
    rows = jnp.arange(n)
    m0 = jnp.full((n, n), _INF, jnp.float32)
    m0 = m0.at[rows[:, None], idx].min(wgt.astype(jnp.float32))
    return m0.at[rows, rows].set(0.0)


def ell_bf_apsp_impl(idx, wgt, *, tile: int = DEFAULT_TILE,
                     max_rounds: int | None = None,
                     use_pallas: bool = False,
                     interpret: bool | None = None):
    """Traceable full closure: (distances d[s, t], rounds executed).
    The carry is relaxed transposed (see module docstring) and flipped
    back on return; symmetric inputs make the flip a no-op in value."""
    n, d_max = idx.shape
    tile = max(1, min(tile, n))
    if max_rounds is None:
        max_rounds = n
    m0 = _full_init(idx, wgt)
    m, rounds = _bf_fixpoint(idx, wgt, m0, tile=tile, max_rounds=max_rounds,
                             use_pallas=use_pallas, interpret=interpret)
    return m.T, rounds


@functools.partial(jax.jit,
                   static_argnames=("tile", "max_rounds", "use_pallas",
                                    "interpret"))
def ell_bf_apsp(idx: jax.Array, wgt: jax.Array, *, tile: int = DEFAULT_TILE,
                max_rounds: int | None = None, use_pallas: bool = False,
                interpret: bool | None = None):
    """All-pairs shortest paths of an ELL-packed graph in one jitted
    program: ``(d[s, t], rounds)``.  ``max_rounds`` (default N, a safe
    cap — convergence takes at most diameter + 1 rounds) is static and
    part of the compile key.  Entries with no path stay ~``_INF``
    (compare against ``_INF / 2``, never equality)."""
    _check_tables(idx, wgt)
    return ell_bf_apsp_impl(idx, wgt, tile=tile, max_rounds=max_rounds,
                            use_pallas=use_pallas, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("s0", "block"))
def _block_init(idx, wgt, *, s0: int, block: int):
    """Transposed one-hop carry for sources [s0, s0 + block): scatter the
    in-block columns of every target row's incoming edges."""
    n = idx.shape[0]
    col = idx - s0
    inblk = (col >= 0) & (col < block)
    m0 = jnp.full((n, block), _INF, jnp.float32)
    m0 = m0.at[jnp.arange(n)[:, None], jnp.clip(col, 0, block - 1)].min(
        jnp.where(inblk, wgt.astype(jnp.float32), _INF))
    return m0.at[s0 + jnp.arange(block), jnp.arange(block)].set(0.0)


@functools.partial(jax.jit,
                   static_argnames=("tile", "max_rounds"),
                   donate_argnums=(2,))
def _block_solve(idx, wgt, m0, *, tile: int, max_rounds: int):
    return _bf_fixpoint(idx, wgt, m0, tile=tile, max_rounds=max_rounds,
                        use_pallas=False, interpret=None)


def ell_bf_apsp_streamed(idx, wgt, *, block: int = DEFAULT_BLOCK,
                         tile: int = DEFAULT_TILE,
                         max_rounds: int | None = None,
                         out: np.ndarray | None = None
                         ) -> tuple[np.ndarray, int]:
    """Memory-frugal full closure: stream source blocks through one
    compiled ``(N, block)`` fixed-point program, writing each converged
    block into a host array.  Returns ``(d[N, N] float32, max rounds
    over blocks)`` — each block early-exits at ITS OWN round count (the
    per-tile convergence contract at source-block granularity).

    Peak memory is the N^2 output + two (N, block) device carries
    (donated ping-pong) + the tables: at N=16384 / block=1024 that is
    ~1.3 GB where any all-device dense method needs >= 2 N^2 live.  The
    one-hop block init uses incoming tables only, so asymmetric weights
    (symmetric pattern) are handled exactly like the full-matrix path.
    """
    idx = jnp.asarray(idx)
    wgt = jnp.asarray(wgt)
    n, _ = _check_tables(idx, wgt)
    block = max(1, min(block, n))
    if n % block:
        raise ValueError(f"ell_bf_apsp_streamed: n={n} must be a multiple "
                         f"of block={block}")
    tile = max(1, min(tile, n))
    if max_rounds is None:
        max_rounds = n
    if out is None:
        out = np.empty((n, n), np.float32)
    elif out.shape != (n, n) or out.dtype != np.float32:
        raise ValueError(f"out must be a float32 ({n}, {n}) array")
    worst = 0
    for s0 in range(0, n, block):
        m0 = _block_init(idx, wgt, s0=s0, block=block)
        m, rounds = _block_solve(idx, wgt, m0, tile=tile,
                                 max_rounds=max_rounds)
        # m[t, s_local] = dist(s0 + s_local -> t): transpose into the
        # output's source-major rows on the host (a view; numpy copies
        # straight into the preallocated slab)
        out[s0:s0 + block, :] = np.asarray(m).T
        worst = max(worst, int(rounds))
    return out, worst

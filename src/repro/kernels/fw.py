"""Blocked Floyd-Warshall APSP: Pallas tiled kernels + a lax.fori fallback.

Repeated (min,+) squaring does ``log2(N)`` full tropical matmuls —
``O(N^3 log N)`` work and, on the pure-jnp path, an ``O(N^3)`` broadcast
per step.  Blocked Floyd-Warshall does the same closure in ONE ``O(N^3)``
pass over 128-aligned tiles with ``O(N^2)`` live memory, which is what
pushes the solvable-N frontier toward 10k switches.

Per pivot tile ``kk`` (classic 4-phase schedule):

1. **pivot block**: close ``D[kk, kk]`` with an in-tile Floyd-Warshall
   (``t`` sequential relaxations);
2. **row panel**:  ``D[kk, :] = min(D[kk, :], P (min,+) D[kk, :])``;
3. **col panel**:  ``D[:, kk] = min(D[:, kk], D[:, kk] (min,+) P)``;
4. **outer update**: ``D = min(D, D[:, kk] (min,+) D[kk, :])``.

Phases 2-4 applied to the pivot row/col/block itself are idempotent
(``P`` has a zero diagonal and is min-plus closed), so the outer update
runs over the whole matrix without masking.

Backend flavors (see ``repro.core.apsp`` for the registry):

* ``fw_apsp_pallas`` — the tiled kernel path (compiled on TPU; the Pallas
  interpreter is the explicit-``interpret=True`` escape hatch used by the
  property tests).  Inside a kernel every slice is static or a masked
  reduction: the pivot closure extracts row/column ``k`` of the tile with
  a ``min`` over an iota mask (exact, so distances are bit-identical to
  the jnp flavor), and the panel updates share ``minplus.minplus_acc``;
* ``fw_apsp_jnp`` — portable ``lax.fori_loop`` Floyd-Warshall (one
  ``O(N^2)`` relaxation per node).  Same algorithm family and identical
  distances; this is what CPU containers run, where the interpreter
  would be the bottleneck.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.minplus import minplus_acc, resolve_interpret

__all__ = ["fw_apsp_pallas", "fw_apsp_jnp", "fw_tile_closure"]


def fw_tile_closure(d: jax.Array) -> jax.Array:
    """In-tile Floyd-Warshall closure of a square (t, t) block: t sequential
    relaxations ``d = min(d, d[:, k] + d[k, :])``.  The whole-matrix body
    of ``fw_apsp_jnp``; the Pallas pivot phase runs the masked twin
    below."""
    t = d.shape[0]

    def body(k, dd):
        row = jax.lax.dynamic_slice_in_dim(dd, k, 1, axis=0)   # (1, t)
        col = jax.lax.dynamic_slice_in_dim(dd, k, 1, axis=1)   # (t, 1)
        return jnp.minimum(dd, col + row)

    return jax.lax.fori_loop(0, t, body, d)


def _tile_closure_masked(d: jax.Array) -> jax.Array:
    """``fw_tile_closure`` as Mosaic lowers it: row and column ``k`` come
    from a ``min`` over an iota mask instead of a dynamic slice of the
    loaded tile (``min(x, inf) == x``, so every relaxation is the same
    float operation as the jnp flavor's)."""
    t = d.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)

    def body(k, dd):
        row = jnp.min(jnp.where(rows == k, dd, jnp.inf), axis=0, keepdims=True)
        col = jnp.min(jnp.where(cols == k, dd, jnp.inf), axis=1, keepdims=True)
        return jnp.minimum(dd, col + row)

    return jax.lax.fori_loop(0, t, body, d)


def _pivot_kernel(d_ref, o_ref):
    o_ref[...] = _tile_closure_masked(d_ref[...])


def _row_panel_kernel(p_ref, r_ref, o_ref):
    o_ref[...] = minplus_acc(r_ref[...], p_ref[...], r_ref[...])


def _col_panel_kernel(c_ref, p_ref, o_ref):
    o_ref[...] = minplus_acc(c_ref[...], c_ref[...], p_ref[...])


def _outer_kernel(d_ref, c_ref, r_ref, o_ref):
    o_ref[...] = minplus_acc(d_ref[...], c_ref[...], r_ref[...])


@functools.partial(jax.jit, static_argnames=("t", "interpret"))
def fw_apsp_pallas(w: jax.Array, *, t: int = 128,
                   interpret: bool | None = None) -> jax.Array:
    """Blocked Floyd-Warshall closure of an (N, N) float32 weight matrix via
    Pallas tiles.  N must be a multiple of the tile size ``t`` (callers pad
    with the +inf sentinel; see ``repro.core.apsp``).  Entries are treated
    additively — any finite "infinity" sentinel survives the single adds."""
    n = w.shape[0]
    if w.ndim != 2 or w.shape[0] != w.shape[1]:
        raise ValueError(f"fw_apsp_pallas: square matrix required, got "
                         f"{w.shape}")
    if n % t:
        raise ValueError(f"fw_apsp_pallas: n={n} must be a multiple of the "
                         f"tile size t={t} (callers pad)")
    interpret = resolve_interpret(interpret)
    nb = n // t
    d = w.astype(jnp.float32)

    row_call = pl.pallas_call(
        _row_panel_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((t, t), lambda j: (0, 0)),
                  pl.BlockSpec((t, t), lambda j: (0, j))],
        out_specs=pl.BlockSpec((t, t), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.float32),
        interpret=interpret)
    col_call = pl.pallas_call(
        _col_panel_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((t, t), lambda i: (i, 0)),
                  pl.BlockSpec((t, t), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((t, t), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, t), jnp.float32),
        interpret=interpret)
    outer_call = pl.pallas_call(
        _outer_kernel,
        grid=(nb, nb),
        in_specs=[pl.BlockSpec((t, t), lambda i, j: (i, j)),
                  pl.BlockSpec((t, t), lambda i, j: (i, 0)),
                  pl.BlockSpec((t, t), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((t, t), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret)
    pivot_call = pl.pallas_call(
        _pivot_kernel,
        out_shape=jax.ShapeDtypeStruct((t, t), jnp.float32),
        interpret=interpret)
    if nb == 1:
        return pivot_call(d)

    # one traced pivot step, rolled over kk with lax.fori_loop: a Python
    # loop here unrolls nb pivot/row/col/outer call groups into the trace
    # (32 at N=4096/t=128), multiplying trace + XLA compile wall for zero
    # runtime benefit — every block offset is already a dynamic slice
    def pivot_step(kk, d):
        piv = jax.lax.dynamic_slice(d, (kk * t, kk * t), (t, t))
        piv = pivot_call(piv)
        row = jax.lax.dynamic_slice(d, (kk * t, 0), (t, n))
        col = jax.lax.dynamic_slice(d, (0, kk * t), (n, t))
        # the row/col panels include the pivot block: min(W, P+W) there is
        # exactly P (zero diagonal), so no masking is needed
        row = row_call(piv, row)
        col = col_call(col, piv)
        d = jax.lax.dynamic_update_slice(d, row, (kk * t, 0))
        d = jax.lax.dynamic_update_slice(d, col, (0, kk * t))
        return outer_call(d, col, row)

    return jax.lax.fori_loop(0, nb, pivot_step, d)


@jax.jit
def fw_apsp_jnp(w: jax.Array) -> jax.Array:
    """Plain Floyd-Warshall: N sequential O(N^2) relaxations, O(N^2) live
    memory.  The portable flavor of the blocked-fw backend (CPU containers,
    CI) — identical distances to the tiled kernel."""
    return fw_tile_closure(w.astype(jnp.float32))

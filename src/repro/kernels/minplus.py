"""Pallas TPU kernel: tropical (min,+) matrix multiply.

    C[i, j] = min_k ( A[i, k] + B[k, j] )

This is the inner loop of all-pairs-shortest-paths by repeated squaring —
the hot spot of the paper's throughput engine (dual MCF solver evaluates
APSP under evolving edge lengths every iteration).

TPU adaptation: the tropical semiring has no MXU support, so the kernel is
blocked exactly like a matmul (HBM -> VMEM tiles, 128-aligned so the VPU
lanes are fully used) but accumulates with elementwise add + min on the
VPU.  The k-dimension is the innermost grid axis; the output block lives
in VMEM across the k-loop and is min-accumulated in place.  Within a
block, k is unrolled statically: step k broadcasts column ``A[:, k]``
across lanes and row ``B[k, :]`` across sublanes, so every slice has a
static offset (Mosaic cannot lower a dynamic slice on the lane axis, nor
``dynamic_slice`` on a loaded value).  ``min`` is exact, so the order in
which k is visited never changes a bit of the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["minplus_matmul_pallas", "minplus_acc", "resolve_interpret"]

_NEG_INF_SAFE = 3.0e38   # "+inf" stand-in that survives adds (python float)


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> auto-detect: run the compiled kernel on TPU, the Pallas
    interpreter everywhere else (CPU containers, CI)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def minplus_acc(acc: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """``min(acc, A (min,+) B)`` for in-kernel values ``a`` (m, K) and
    ``b`` (K, n), K unrolled with static offsets (shared by the blocked
    Floyd-Warshall kernels)."""
    for kk in range(a.shape[1]):
        acc = jnp.minimum(acc, a[:, kk:kk + 1] + b[kk:kk + 1, :])
    return acc


def _minplus_kernel(a_ref, b_ref, o_ref):
    """One (bm, bn) output tile; min-accumulate over the k grid axis."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, _NEG_INF_SAFE)

    o_ref[...] = minplus_acc(o_ref[...], a_ref[...], b_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus_matmul_pallas(a: jax.Array, b: jax.Array, *,
                          bm: int = 128, bn: int = 128, bk: int = 128,
                          interpret: bool | None = None) -> jax.Array:
    """Tropical matmul via pallas_call.  Inputs are (M, K) and (K, N) float32;
    entries >= 1e38 are treated as +inf.  Shapes must be multiples of the
    block sizes (callers pad; see ops.minplus_matmul).  ``interpret=None``
    auto-detects from the JAX backend (compiled on TPU, interpreter
    elsewhere)."""
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(
            f"minplus_matmul_pallas: inner dimensions disagree: "
            f"a.shape={a.shape} (K={k}) vs b.shape={b.shape} (K={k2})")
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"minplus_matmul_pallas: shapes must be multiples of the block "
            f"sizes: a.shape={a.shape}, b.shape={b.shape} with blocks "
            f"(bm={bm}, bn={bn}, bk={bk}); callers pad (see ops.minplus_matmul)")

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _minplus_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(a.astype(jnp.float32), b.astype(jnp.float32))

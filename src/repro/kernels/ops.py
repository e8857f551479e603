"""Jit'd public wrappers around the Pallas kernels.

* pad/unpad to block multiples,
* interpret-mode dispatch: ``interpret=None`` auto-detects via
  ``jax.default_backend()`` (compiled kernels on TPU, the Pallas
  interpreter on CPU containers); pass an explicit bool to override,
* custom VJPs so kernels can sit inside differentiable code (the MCF dual
  solver differentiates through min-plus APSP).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import minplus as _minplus
from repro.kernels import flash_attention as _flash
from repro.kernels import ref as _ref
from repro.kernels.minplus import resolve_interpret

__all__ = ["minplus_matmul", "flash_attention", "wkv_chunked", "INF",
           "resolve_interpret"]

INF = 1.0e38   # "infinity" edge weight that survives one add without overflow


def _pad_to(x: jax.Array, m0: int, m1: int, val: float) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 == 0 and p1 == 0:
        return x
    return jnp.pad(x, ((0, p0), (0, p1)), constant_values=val)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def minplus_matmul(a: jax.Array, b: jax.Array, block: int = 128,
                   interpret: bool | None = None) -> jax.Array:
    """C = A (min,+) B with padding to block multiples (``INF`` rows and
    columns, which never win a min), so every size runs the kernel.
    Differentiable: the VJP routes cotangents through the argmin edges
    (ties split evenly), which is exactly the shortest-path-DAG
    subgradient the MCF solver needs.  ``interpret=None`` auto-detects
    from the backend (compiled on TPU)."""
    m, _ = a.shape
    _, n = b.shape
    ap = _pad_to(a.astype(jnp.float32), block, block, INF)
    bp = _pad_to(b.astype(jnp.float32), block, block, INF)
    out = _minplus.minplus_matmul_pallas(ap, bp, bm=block, bn=block,
                                         bk=block, interpret=interpret)
    return out[:m, :n]


def _minplus_fwd(a, b, block, interpret):
    c = minplus_matmul(a, b, block, interpret)
    return c, (a, b, c)


def _minplus_bwd(block, interpret, res, g):
    a, b, c = res
    # mask[i, k, j] = 1 where A[i,k] + B[k,j] == C[i,j]; split ties evenly.
    # The tie tolerance must scale with the entries: the primal MCF solver
    # differentiates APSP at edge lengths spanning many orders of
    # magnitude, and an absolute 1e-6 would lump near-ties of tiny-length
    # paths into the "shortest" set.
    s = a[:, :, None] + b[None, :, :]
    tol = 1e-6 * jnp.maximum(jnp.abs(c[:, None, :]), 1e-6)
    mask = (s <= c[:, None, :] + tol).astype(jnp.float32)
    mask = mask / jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)
    da = jnp.einsum("ikj,ij->ik", mask, g)
    db = jnp.einsum("ikj,ij->kj", mask, g)
    return da, db


minplus_matmul.defvjp(_minplus_fwd, _minplus_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = True) -> jax.Array:
    """Padded GQA flash attention.  q: [B, Lq, Hq, D]; k, v: [B, Lk, Hkv, D].

    Pads Lq/Lk up to tile multiples; padded keys are masked via lk_valid,
    padded query rows are discarded.  Falls back to the jnp reference for
    shapes smaller than one tile (e.g. single-token decode on tiny models,
    where a kernel launch would be all overhead).
    """
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    if lq == 1 or lk < bk:
        return _ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    pq = (-lq) % bq
    pk = (-lk) % bk
    # pad queries at the FRONT so the causal diagonal stays aligned with the
    # end of the (unpadded) key sequence; padded keys go at the back and are
    # masked via lk_valid.
    qp = jnp.pad(q, ((0, 0), (pq, 0), (0, 0), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v
    out = _flash.flash_attention_pallas(
        qp, kp, vp, causal=causal, scale=scale, bq=bq, bk=bk,
        lk_valid=lk, interpret=interpret)
    return out[:, pq:]


def wkv_chunked(r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array,
                u: jax.Array, interpret: bool = True) -> jax.Array:
    """Chunked WKV-6 via the Pallas kernel; pads T to the chunk size."""
    from repro.kernels import wkv as _wkv
    bh, t, n = r.shape
    pad = (-t) % _wkv.CHUNK
    if pad:
        # padded steps: k,v = 0 and log_w = 0 leave the state untouched
        r, k, v, log_w = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                          for x in (r, k, v, log_w))
    out = _wkv.wkv_chunked_pallas(r, k, v, log_w, u, interpret=interpret)
    return out[:, :t]

"""Plain reference of one certified throughput bracket.

The system under test answers, for a fabric (symmetric link capacities
``cap[N, N]``) and a switch-level demand ``dem[N, N]``, a bracket
``lb <= theta* <= ub`` on the max-concurrent-flow rate, from a fixed
number of iterations of one algorithm (Singla et al., NSDI 2014, Sec. 3;
the certificate is the usual Frank-Wolfe / length-function pair):

* edge lengths ``l = exp(z)`` descend ``log D(l) - log alpha(l)`` by Adam
  with a cosine learning rate, where ``D = sum cap * l`` and ``alpha =
  sum dem * dist_l``; every iterate certifies ``ub = D / alpha``;
* the shortest-path routing of all demand under ``l`` (ties split evenly
  among the tight predecessors of each node) is a Frank-Wolfe direction;
  the flow blends it in by a ternary line search on the maximum link
  utilisation, and every blend certifies ``lb = 1 / max utilisation``.

This file computes the same thing the plain way, on a neighbour list built
here from ``cap``: Bellman-Ford for the distances, a hop-by-hop walk back
along the tight edges for the routing.  It shares no code with the
system, and ``dtype`` lets the control run the same steps in a lower
precision.  Edge state lives per directed edge ``(k -> t)`` in the slot
``(t, j)`` with ``nbr[t, j] = k``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INF = 1.0e18           # length of a missing edge
TIE_REL = 1e-6         # relative slack under which two path lengths tie
LINE_SEARCH_STEPS = 24


def neighbour_tables(caps: list[np.ndarray]):
    """Incoming neighbour tables of several same-size fabrics, padded to
    one width: ``nbr[b, t, j]`` is the j-th predecessor of ``t``,
    ``valid`` marks real slots, ``slot_cap`` their capacity, and
    ``rev[b, k, i]`` lists the flat slots ``t * width + j`` whose edge
    leaves ``k`` (padding points one past the last slot)."""
    n = caps[0].shape[0]
    width = max(int((c > 0).sum(axis=0).max()) for c in caps)
    out_w = max(int((c > 0).sum(axis=1).max()) for c in caps)
    nb = len(caps)
    nbr = np.tile(np.arange(n, dtype=np.int32)[None, :, None], (nb, 1, width))
    valid = np.zeros((nb, n, width), bool)
    slot_cap = np.zeros((nb, n, width), np.float64)
    rev = np.full((nb, n, out_w), n * width, np.int32)
    for b, cap in enumerate(caps):
        fill = np.zeros(n, np.int64)
        for t in range(n):
            ks = np.flatnonzero(cap[:, t] > 0)
            nbr[b, t, :len(ks)] = ks
            valid[b, t, :len(ks)] = True
            slot_cap[b, t, :len(ks)] = cap[ks, t]
            for j, k in enumerate(ks):
                rev[b, k, fill[k]] = t * width + j
                fill[k] += 1
    return nbr, valid, slot_cap, rev


def _distances(w, nbr, valid, dtype):
    """Bellman-Ford, transposed: ``m[t, s]`` = shortest length s -> t."""
    n = nbr.shape[0]
    eye = jnp.eye(n, dtype=bool)
    w = jnp.where(valid, w, INF).astype(dtype)
    m0 = jnp.where(eye, 0.0, INF).astype(dtype)

    def relax(carry):
        m, _, r = carry
        cand = jnp.min(m[nbr] + w[:, :, None], axis=1)   # (t, s)
        new = jnp.minimum(m, cand)
        return new, jnp.any(new < m), r + 1

    m, _, _ = jax.lax.while_loop(lambda c: c[1] & (c[2] < n), relax,
                                 (m0, jnp.bool_(True), 0))
    return m


def _route(m, w, nbr, valid, rev, dem_t, dtype):
    """Load per edge slot when every demand follows its shortest paths,
    split evenly among tight predecessors at each node."""
    n, width = nbr.shape
    eye = jnp.eye(n, dtype=bool)
    w = jnp.where(valid, w, INF).astype(dtype)
    via = m[nbr] + w[:, :, None]                       # (t, j, s)
    tol = TIE_REL * jnp.maximum(jnp.abs(m), 1e-6)
    tight = (via <= (m + tol)[:, None, :]) & valid[:, :, None]
    count = tight.sum(axis=1, keepdims=True).astype(dtype)
    share = tight.astype(dtype) / jnp.maximum(count, 1.0)
    reach = m < INF / 2
    u0 = jnp.where(reach & ~eye, dem_t, 0.0).astype(dtype)

    def hop(carry):
        u, loads, h = carry
        moved = share * u[:, None, :]                  # (t, j, s)
        loads = loads + moved.sum(axis=2)
        flat = jnp.concatenate(
            [moved.reshape(n * width, n), jnp.zeros((1, n), dtype)])
        back = flat[rev].sum(axis=1)                   # (k, s)
        return jnp.where(eye, 0.0, back), loads, h + 1

    def more(carry):
        return jnp.any(carry[0] != 0.0) & (carry[2] < n)

    _, loads, _ = jax.lax.while_loop(
        more, hop, (u0, jnp.zeros((n, width), dtype), 0))
    return loads


def bracket(nbr, valid, slot_cap, rev, dem, *, iters: int, lr: float,
            tol: float = 0.0, check_every: int = 25, dtype=jnp.float32):
    """(lb, ub) of one fabric after ``iters`` iterations, or earlier once
    the gap (ub - lb) / ub shrank by less than ``tol`` over the last
    ``check_every`` of them."""
    n = nbr.shape[0]
    cap = jnp.where(valid, slot_cap, 0.0).astype(dtype)
    dem_t = dem.T.astype(dtype)
    safe_cap = jnp.where(valid, cap, 1.0)
    one = jnp.asarray(1.0, dtype)

    def alpha_of(m):
        return jnp.sum(m * dem_t)

    routable = alpha_of(_distances(jnp.ones_like(cap), nbr, valid,
                                   dtype)) < INF / 2

    def step(state):
        i, z, mo, vo, loads, best_lb, best_ub, ref_gap, _ = state
        l = jnp.where(valid, jnp.exp(z), 0.0)
        m = _distances(l, nbr, valid, dtype)
        alpha = alpha_of(m)
        sp = _route(m, l, nbr, valid, rev, dem_t, dtype)
        d_val = jnp.sum(cap * l)
        best_ub = jnp.minimum(best_ub, d_val / alpha)

        g = l * (cap / d_val - sp / alpha)
        t = i + 1
        rate = (lr * 0.5 * (1 + jnp.cos(jnp.pi * i / iters)) + 1e-3
                ).astype(dtype)
        mo = 0.9 * mo + 0.1 * g
        vo = 0.999 * vo + 0.001 * g * g
        mh = mo / (1 - 0.9 ** t).astype(dtype)
        vh = vo / (1 - 0.999 ** t).astype(dtype)
        z = jnp.where(valid, z - rate * mh / (jnp.sqrt(vh) + 1e-8), 0.0)

        u_cur = jnp.where(valid, loads / safe_cap, 0.0)
        u_sp = jnp.where(valid, sp / safe_cap, 0.0)

        def umax(gam):
            return jnp.max((1 - gam) * u_cur + gam * u_sp)

        lo, hi = jnp.asarray(0.0, dtype), one
        for _ in range(LINE_SEARCH_STEPS):
            a = lo + (hi - lo) / 3
            b = hi - (hi - lo) / 3
            fa, fb = umax(a), umax(b)
            lo = jnp.where(fa < fb, lo, a)
            hi = jnp.where(fa < fb, b, hi)
        gam = jnp.maximum((lo + hi) / 2, (1.0 / (t + 1.0)).astype(dtype))
        gam = jnp.where(i == 0, one, gam)
        loads = (1 - gam) * loads + gam * sp
        u = umax(gam)
        lb = jnp.where(u > 0, 1.0 / jnp.maximum(u, 1e-30), 0.0).astype(dtype)
        best_lb = jnp.maximum(best_lb, lb)

        at_check = t % check_every == 0
        gap = (best_ub - best_lb) / jnp.maximum(best_ub, 1e-30)
        done = at_check & (ref_gap - gap < tol)
        ref_gap = jnp.where(at_check, gap, ref_gap)
        return t, z, mo, vo, loads, best_lb, best_ub, ref_gap, done

    zero = jnp.zeros(valid.shape, dtype)
    init = (0, zero, zero, zero, zero, jnp.asarray(0.0, dtype),
            jnp.asarray(jnp.inf, dtype), jnp.asarray(jnp.inf, dtype),
            jnp.bool_(False))
    state = jax.lax.while_loop(lambda s: (s[0] < iters) & ~s[-1], step,
                               init)
    best_lb, best_ub = state[5], state[6]
    return jnp.where(routable, best_lb, 0.0), best_ub


@functools.partial(jax.jit, static_argnames=("iters", "lr", "tol",
                                             "check_every", "dtype"))
def _brackets(nbr, valid, slot_cap, rev, dem, *, iters, lr, tol, check_every,
              dtype):
    return jax.vmap(functools.partial(
        bracket, iters=iters, lr=lr, tol=tol, check_every=check_every,
        dtype=dtype))(nbr, valid, slot_cap, rev, dem)


def brackets(caps, dems, *, iters: int, lr: float, tol: float = 0.0,
             check_every: int = 25, dtype=jnp.float32,
             block: int = 4) -> np.ndarray:
    """``[B, 2]`` float64 array of (lb, ub), fabrics ``block`` at a time."""
    out = []
    for lo in range(0, len(caps), block):
        cb, db = list(caps[lo:lo + block]), list(dems[lo:lo + block])
        real = len(cb)
        cb += [cb[0]] * (block - real)   # one program shape for every block
        db += [db[0]] * (block - real)
        nbr, valid, slot_cap, rev = neighbour_tables(cb)
        lb, ub = _brackets(jnp.asarray(nbr), jnp.asarray(valid),
                           jnp.asarray(slot_cap, jnp.float32),
                           jnp.asarray(rev),
                           jnp.asarray(np.stack(db), jnp.float32),
                           iters=iters, lr=lr, tol=tol,
                           check_every=check_every, dtype=dtype)
        got = np.stack([np.asarray(lb, np.float64),
                        np.asarray(ub, np.float64)], axis=1)
        out.append(got[:real])
    return np.concatenate(out) if out else np.zeros((0, 2))

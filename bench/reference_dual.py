"""Plain reference of the designer's ranking bound: the upper half of
``bench/reference.py``'s bracket, stopped the way the ranking program
stops.

The designer ranks candidates by a certified upper bound alone: edge
lengths ``l = exp(z)`` descend ``log D(l) - log alpha(l)`` by Adam with a
cosine learning rate, and every iterate certifies ``ub = D / alpha``.  It
stops after ``iters`` iterations, or earlier once the best bound improved
by less than ``tol`` (relative to it) over the last ``check_every`` of
them.  The distances and the shortest-path routing (the descent's
subgradient) are ``bench/reference.py``'s, on the same neighbour tables;
nothing here is the system's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import _distances, _route, neighbour_tables


def upper(nbr, valid, slot_cap, rev, dem, *, iters: int, lr: float,
          tol: float = 0.0, check_every: int = 25, dtype=jnp.float32):
    """(ub, iterations) of one fabric."""
    cap = jnp.where(valid, slot_cap, 0.0).astype(dtype)
    dem_t = dem.T.astype(dtype)

    def step(state):
        i, z, mo, vo, best, ref_best, _ = state
        l = jnp.where(valid, jnp.exp(z), 0.0)
        m = _distances(l, nbr, valid, dtype)
        alpha = jnp.sum(m * dem_t)
        sp = _route(m, l, nbr, valid, rev, dem_t, dtype)
        d_val = jnp.sum(cap * l)
        best = jnp.minimum(best, d_val / alpha)
        g = l * (cap / d_val - sp / alpha)
        t = i + 1
        rate = (lr * 0.5 * (1 + jnp.cos(jnp.pi * i / iters)) + 1e-3
                ).astype(dtype)
        mo = 0.9 * mo + 0.1 * g
        vo = 0.999 * vo + 0.001 * g * g
        mh = mo / (1 - 0.9 ** t).astype(dtype)
        vh = vo / (1 - 0.999 ** t).astype(dtype)
        z = jnp.where(valid, z - rate * mh / (jnp.sqrt(vh) + 1e-8), 0.0)
        at_check = t % check_every == 0
        done = at_check & ((ref_best - best) / jnp.maximum(best, 1e-30)
                           < tol)
        ref_best = jnp.where(at_check, best, ref_best)
        return t, z, mo, vo, best, ref_best, done

    zero = jnp.zeros(valid.shape, dtype)
    inf = jnp.asarray(jnp.inf, dtype)
    state = jax.lax.while_loop(lambda s: (s[0] < iters) & ~s[-1], step,
                               (0, zero, zero, zero, inf, inf,
                                jnp.bool_(False)))
    return state[4], state[0]


@functools.partial(jax.jit, static_argnames=("iters", "lr", "tol",
                                             "check_every", "dtype"))
def _uppers(nbr, valid, slot_cap, rev, dem, *, iters, lr, tol, check_every,
            dtype):
    return jax.vmap(functools.partial(
        upper, iters=iters, lr=lr, tol=tol, check_every=check_every,
        dtype=dtype))(nbr, valid, slot_cap, rev, dem)


def uppers(caps, dems, *, iters: int, lr: float, tol: float = 0.0,
           check_every: int = 25, dtype=jnp.float32,
           block: int = 4) -> np.ndarray:
    """``[B]`` float64 array of upper bounds, fabrics ``block`` at a
    time."""
    out = []
    for lo in range(0, len(caps), block):
        cb, db = list(caps[lo:lo + block]), list(dems[lo:lo + block])
        real = len(cb)
        cb += [cb[0]] * (block - real)   # one program shape for every block
        db += [db[0]] * (block - real)
        nbr, valid, slot_cap, rev = neighbour_tables(cb)
        ub, _ = _uppers(jnp.asarray(nbr), jnp.asarray(valid),
                        jnp.asarray(slot_cap, jnp.float32),
                        jnp.asarray(rev),
                        jnp.asarray(np.stack(db), jnp.float32),
                        iters=iters, lr=lr, tol=tol,
                        check_every=check_every, dtype=dtype)
        out.append(np.asarray(ub, np.float64)[:real])
    return np.concatenate(out) if out else np.zeros(0)

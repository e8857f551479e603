"""Fabric family ``random_regular``: the Jellyfish construction (Singla et
al., NSDI 2012, arXiv 1110.1687).  Every switch has ``network_ports``
links to other switches and ``servers_per_switch`` servers; port stubs
are paired at random, then self-loops and parallel links are removed by
random double-edge swaps; disconnected draws are redrawn.

Configuration keys (``fabric``): ``switches``, ``network_ports``,
``servers_per_switch``.
"""
from __future__ import annotations

import numpy as np

from bench import gen


def servers(spec: dict) -> np.ndarray:
    return np.full(spec["switches"], spec["servers_per_switch"])


def stand_in(spec: dict) -> np.ndarray:
    return gen.cliques(spec["switches"], spec["network_ports"])


def _connected(adj: np.ndarray) -> bool:
    seen = np.zeros(len(adj), bool)
    seen[0] = True
    frontier = seen.copy()
    while frontier.any():
        nxt = adj[frontier].any(axis=0) & ~seen
        seen |= nxt
        frontier = nxt
    return bool(seen.all())


def build(spec: dict, rng: np.random.Generator) -> np.ndarray:
    """Capacity matrix (0/1, float64) of a connected simple regular
    graph."""
    n, degree = spec["switches"], spec["network_ports"]
    if n * degree % 2 or degree >= n:
        raise ValueError(f"no simple {degree}-regular graph on {n} nodes")
    for _attempt in range(100):
        stubs = rng.permutation(np.repeat(np.arange(n), degree))
        edges = stubs.reshape(-1, 2).copy()
        key = {}
        for i, (u, v) in enumerate(edges):
            key.setdefault((min(u, v), max(u, v)), []).append(i)

        def bad(i):
            u, v = edges[i]
            return u == v or len(key[(min(u, v), max(u, v))]) > 1

        todo = [i for i in range(len(edges)) if bad(i)]
        for _ in range(100 * len(edges)):
            todo = [i for i in todo if bad(i)]
            if not todo:
                break
            i = todo[0]
            j = int(rng.integers(len(edges)))
            (a, b), (c, d) = edges[i], edges[j]
            if rng.random() < 0.5:
                c, d = d, c
            new = ((min(a, c), max(a, c)), (min(b, d), max(b, d)))
            if a == c or b == d or new[0] == new[1] or new[0] in key \
                    or new[1] in key or i == j:
                continue
            for e, (u, v) in ((i, (a, b)), (j, edges[j])):
                lst = key[(min(u, v), max(u, v))]
                lst.remove(e)
                if not lst:
                    del key[(min(u, v), max(u, v))]
            edges[i], edges[j] = (a, c), (b, d)
            key[new[0]] = [i]
            key[new[1]] = [j]
        if todo:
            continue
        cap = np.zeros((n, n))
        cap[edges[:, 0], edges[:, 1]] = 1.0
        cap[edges[:, 1], edges[:, 0]] = 1.0
        if _connected(cap > 0):
            return cap
    raise RuntimeError(f"no connected simple {degree}-regular graph on {n} "
                       "nodes in 100 draws")

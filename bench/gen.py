"""Instance generation of the benchmark, kept here so the yardstick does not
move when the system's own generators change.

A fabric family is ``bench/fabrics/<family>.py`` (``build(spec, rng)`` ->
capacity matrix, ``servers(spec)`` -> servers per switch, ``stand_in(spec)``
-> a cheap fabric of the same shapes) and a traffic
pattern is ``bench/traffic/<pattern>.py`` (``demand(servers, rng)`` ->
switch-level demand); both are found by the name the configuration or
the workload gives.

Every draw comes from a ``numpy.random.Generator`` the caller keys, so
the same key gives the same instances.
"""
from __future__ import annotations

import hashlib

import numpy as np

from bench.files import load_module


def rng_for(*key: int) -> np.random.Generator:
    """A generator keyed on any tuple of whole numbers (the run's seed,
    the call index, the lane), each taken modulo 2**64."""
    return np.random.default_rng([int(k) % (1 << 64) for k in key])


def fabric(spec: dict, rng: np.random.Generator) -> np.ndarray:
    """Capacity matrix of the configuration's ``fabric`` (by ``family``)."""
    return load_module("fabrics", spec["family"]).build(spec, rng)


def servers(spec: dict) -> np.ndarray:
    return load_module("fabrics", spec["family"]).servers(spec)


def stand_in(spec: dict) -> np.ndarray:
    """A fabric of the family's shapes (switches, table width) that costs
    little to solve, for warm-up."""
    return load_module("fabrics", spec["family"]).stand_in(spec)


def cliques(n: int, degree: int) -> np.ndarray:
    """Disjoint cliques of ``degree + 1`` switches: the shapes and table
    width of a ``degree``-regular fabric on ``n`` switches, with a
    diameter of one."""
    cap = np.zeros((n, n))
    size = degree + 1
    for lo in range(0, n - size + 1, size):
        cap[lo:lo + size, lo:lo + size] = 1.0
    np.fill_diagonal(cap, 0.0)
    return cap


def traffic(pattern: str, servers: np.ndarray, rng: np.random.Generator
            ) -> np.ndarray:
    """The demand of the workload's named ``pattern``."""
    return load_module("traffic", pattern).demand(servers, rng)


def digest(*arrays: np.ndarray) -> str:
    """Short sha256 over the arrays' bytes, to name what a call received."""
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a, np.float64).tobytes())
    return h.hexdigest()[:16]

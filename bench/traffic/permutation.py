"""Traffic pattern ``permutation``: every server sends one unit flow to
one other server and receives one (a random derangement), summed into a
switch-level demand; flows inside one switch never enter the network."""
from __future__ import annotations

import numpy as np


def demand(servers: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Switch-level demand ``dem[u, v]`` (float64)."""
    switch_of = np.repeat(np.arange(len(servers)), servers)
    total = len(switch_of)
    dst = rng.permutation(total)
    while True:
        fixed = np.flatnonzero(dst == np.arange(total))
        if not len(fixed):
            break
        if len(fixed) == 1:
            j = (fixed[0] + 1) % total
            dst[fixed[0]], dst[j] = dst[j], dst[fixed[0]]
        else:
            dst[fixed] = dst[np.roll(fixed, 1)]
    dem = np.zeros((len(servers), len(servers)))
    src, tgt = switch_of, switch_of[dst]
    keep = src != tgt
    np.add.at(dem, (src[keep], tgt[keep]), 1.0)
    return dem

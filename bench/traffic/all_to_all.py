"""Traffic pattern ``all_to_all``: every server sends one unit in total,
split equally over every other server, summed into a switch-level demand;
flows inside one switch never enter the network.  The uniform traffic
under which the paper states its bound; it draws nothing from ``rng``."""
from __future__ import annotations

import numpy as np


def demand(servers: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Switch-level demand ``dem[u, v]`` (float64)."""
    servers = np.asarray(servers, np.float64)
    dem = np.outer(servers, servers) / (servers.sum() - 1.0)
    np.fill_diagonal(dem, 0.0)
    return dem

"""Entry ``combined_sweep``: the paper's Fig. 6 design sweep as a user runs
it, ``heterogeneous.combined_sweep`` on the certified engine.  The call
builds every two-class fabric on the host and solves them in one
``solve_batch``, so the build is inside the timed span.

The sweep draws its fabrics itself from its ``seed0``, and how long the
build takes depends on them.  So that every run does the same work, call
``k``'s ``seed0`` is the workload's pool entry ``k mod pool.calls``, drawn
from ``pool.key``; the run's seed draws the sample the reference checks.

Configuration keys: ``pool`` (``TwoClassSpec`` fields) and ``solver``.
Workload keys: ``sweep`` (``server_splits``, ``cross_bias``, ``runs``,
``pool``) and ``engine`` (keyword arguments of ``get_engine``).
"""
from __future__ import annotations

import numpy as np

from bench import gen


class Recorder:
    """Engine wrapper that keeps the lanes a sweep aggregates away, and
    spans the engine's ``solve_batch``."""

    def __init__(self, engine, run):
        self.engine, self.run = engine, run
        self.topos, self.dems, self.results = [], [], []

    def solve_batch(self, topos, dems):
        with self.run.span("solve_batch"):
            self.results = self.engine.solve_batch(topos, dems)
        self.topos, self.dems = topos, dems
        return self.results


def _lanes(sweep: dict) -> int:
    return (len(sweep["server_splits"]) * len(sweep["cross_bias"])
            * sweep["runs"])


def setup(run) -> dict:
    from repro.core import get_engine
    pool = run.cfg["pool"]
    engine = get_engine(run.solver["engine"], **run.engine_kwargs())
    n = pool["n_large"] + pool["n_small"]
    cap = gen.cliques(n, pool["k_small"] - 1)
    lanes = _lanes(run.wl["sweep"])
    engine.solve_batch([cap] * lanes, [cap] * lanes)
    return {"engine": engine, "run": run, "points": []}


def prepare(state: dict, k: int):
    pool = state["run"].wl["sweep"]["pool"]
    # seeds of one call stay below 2**31 with the sweep's offsets added
    seed0 = int(gen.rng_for(pool["key"], k % pool["calls"]).integers(1 << 30))
    return seed0, {"seed0": seed0}


def call(state: dict, seed0: int):
    from repro.core import heterogeneous as het
    run = state["run"]
    sweep = run.wl["sweep"]
    rec = Recorder(state["engine"], run)
    points = het.combined_sweep(
        het.TwoClassSpec(**run.cfg["pool"]),
        [tuple(s) for s in sweep["server_splits"]], sweep["cross_bias"],
        runs=sweep["runs"], seed0=seed0, engine=rec)
    lanes = [{"cap": np.asarray(t.cap, np.float64),
              "dem": np.asarray(d, np.float64),
              "servers": np.asarray(t.servers), "lb": r.meta["lb"],
              "ub": r.meta["ub"], "iterations": r.meta["iterations"]}
             for t, d, r in zip(rec.topos, rec.dems, rec.results)]
    state["points"].append((points, lanes))
    return lanes, {"fabrics": len(lanes),
                   "digest": gen.digest(*[x["cap"] for x in lanes],
                                        *[x["dem"] for x in lanes])}


def check(state: dict, run) -> dict:
    """``bad_instances``: fabrics that break the pool (a switch with more
    links and servers than ports, servers not as the split says, a
    capacity matrix that is not a symmetric multigraph), and sweep points
    whose reported means are not those of their lanes."""
    pool, sweep = run.cfg["pool"], run.wl["sweep"]
    nl = pool["n_large"]
    per_split = len(sweep["cross_bias"]) * sweep["runs"]
    bad = 0
    for points, lanes in state["points"]:
        for i, lane in enumerate(lanes):
            per_l, per_s = sweep["server_splits"][i // per_split]
            cap, srv = lane["cap"], lane["servers"]
            ports = np.where(np.arange(len(cap)) < nl, pool["k_large"],
                             pool["k_small"])
            ok = (np.array_equal(cap, cap.T) and not np.diag(cap).any()
                  and np.array_equal(cap, np.round(cap)) and cap.min() >= 0
                  and (srv[:nl] == per_l).all() and (srv[nl:] == per_s).all()
                  and (cap.sum(axis=1) + srv <= ports).all())
            bad += not ok
        flat = [p for split in points.values() for p in split]
        for j, p in enumerate(flat):
            rs = lanes[j * sweep["runs"]:(j + 1) * sweep["runs"]]
            ub = np.mean([x["ub"] for x in rs])
            lb = np.mean([x["lb"] for x in rs])
            bad += not (np.isclose(p.mean, ub, rtol=1e-12)
                        and np.isclose(p.lb_mean, lb, rtol=1e-12))
    return {"bad_instances": {"value": int(bad),
                              "limit": run.wl["limits"]["bad_instances"]}}

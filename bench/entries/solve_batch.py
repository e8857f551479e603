"""Entry ``solve_batch``: the engine's ``solve_batch`` on fabrics and
traffic the benchmark draws itself (``bench/gen.py``), between calls and
outside the timed span.

Every run does the same work: call ``k``'s fabrics and demands are the
workload's pool group ``k mod pool.calls``, drawn from ``pool.key``; the
run's seed draws the brackets the reference checks.  Fabrics drawn from
the seed would change the work with it (hop counts, and so call times,
differ from fabric to fabric).

Configuration keys: ``fabric`` (``family`` and its sizes) and ``solver``.
Workload keys: ``traffic`` (``pattern``, ``fabrics_per_call``, ``pool``:
``key`` and ``calls``) and ``engine`` (keyword arguments of
``get_engine``, e.g. ``devices``).
"""
from __future__ import annotations

from bench import gen


def setup(run) -> dict:
    from repro.core import get_engine
    fab = run.cfg["fabric"]
    engine = get_engine(run.solver["engine"], **run.engine_kwargs())
    lanes = run.wl["traffic"]["fabrics_per_call"]
    cap = gen.stand_in(fab)
    # demand between neighbours only: one hop back per descent step
    engine.solve_batch([cap] * lanes, [cap] * lanes)
    return {"engine": engine, "lanes": lanes, "run": run}


def prepare(state: dict, k: int):
    run = state["run"]
    fab, traffic = run.cfg["fabric"], run.wl["traffic"]
    pool = traffic["pool"]
    servers = gen.servers(fab)
    caps, dems = [], []
    for lane in range(state["lanes"]):
        rng = gen.rng_for(pool["key"], k % pool["calls"], lane)
        caps.append(gen.fabric(fab, rng))
        dems.append(gen.traffic(traffic["pattern"], servers, rng))
    return (caps, dems), {"fabrics": len(caps),
                          "digest": gen.digest(*caps, *dems)}


def call(state: dict, inputs):
    caps, dems = inputs
    with state["run"].span("solve_batch"):
        res = state["engine"].solve_batch(caps, dems)
    lanes = [{"cap": c, "dem": d, "lb": r.meta["lb"], "ub": r.meta["ub"],
              "iterations": r.meta["iterations"]}
             for c, d, r in zip(caps, dems, res)]
    return lanes, None

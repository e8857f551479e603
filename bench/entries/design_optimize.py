"""Entry ``design_optimize``: the paper's Sec. 7 rewiring search as a user
runs it, ``repro.design.optimize`` over ``VL2Space`` with the designer's
own ranking engine.  One call is one search: the initial fleet and each
round are ranking executes of the dual program (``refill`` after the
first), then one certification execute of the primal program over the
elites and the recipe.

The call's brackets (what ``brackets_per_s`` and ``gap_mean_pct`` read,
and ``bench/compare.py`` checks) are the certification lanes.  The
ranking lanes (a dual upper bound each) stay in the entry's state for
``check``.  Both are recorded where the plan executes them
(``BatchPlan.execute``), so what is checked is what the program solved.

So that every run does the same work, call ``k`` searches with the
workload's pool entry ``(pool.start + k) mod pool.calls``, drawn from
``pool.key``; the run's seed draws only the samples the reference checks.
``pool.start`` sets which searches the window's first calls run, and so
how far from the end of the window the last call starts.

Configuration keys: ``equipment`` (``d_a``, ``d_i``, ``servers_per_tor``,
``n_tor``, ``fabric_gbps``, ``server_gbps``) and ``solver``.  Workload
keys: ``search`` (``moves``, ``fleet``, ``runs``, ``elite``, ``rounds``,
``pool``), ``engine`` (keyword arguments of ``get_engine``) and
``reference`` (``lanes`` certification and ``rank_lanes`` ranking lanes
recomputed, ``block``).
"""
from __future__ import annotations

import contextlib

import numpy as np

from bench import compare, gen

RANK_SAMPLE_KEY = 0x5EED + 1


def _space(run):
    from repro.core import vl2
    from repro.design import VL2Space
    eq = run.cfg["equipment"]
    return VL2Space(vl2.VL2Spec(eq["d_a"], eq["d_i"], eq["servers_per_tor"]),
                    eq["n_tor"])


def _switches(eq: dict) -> int:
    return eq["n_tor"] + eq["d_i"] + eq["d_a"] // 2


def _pool_seed(pool: dict, k: int) -> int:
    group = (pool["start"] + k) % pool["calls"]
    return int(gen.rng_for(pool["key"], group).integers(1 << 30))


def setup(run) -> dict:
    """The ranking engine, warmed with both programs at every shape a
    search dispatches: ``fleet x runs`` ranking lanes, and ``(elite + 1)
    x runs`` or ``elite x runs`` certification lanes (the recipe is
    certified as one more candidate unless it is an elite).

    First, the recipe each pool group starts from must be the
    configuration's equipment (``recipe_fault``): a program that cannot
    build the deployment stops here, before anything is timed."""
    from repro.core import get_engine
    search = run.wl["search"]
    eq = run.cfg["equipment"]
    for k in range(search["pool"]["calls"]):
        recipe = _space(run).initial(_pool_seed(search["pool"], k)).topo
        if recipe_fault(recipe.cap.sum(axis=0), eq):
            raise RuntimeError(
                f"the recipe of pool call {k} is not the configuration's "
                "equipment: a switch is wired past its ports or a ToR "
                "lacks its two uplinks")
    kw = run.engine_kwargs()
    engine = get_engine(run.solver["engine"], **kw)
    cap = gen.cliques(_switches(eq), 5)
    ranking = search["fleet"] * search["runs"]
    engine.solve_batch([cap] * ranking, [cap] * ranking)
    certify = get_engine("primal", **kw)
    for cands in (search["elite"], search["elite"] + 1):
        lanes = cands * search["runs"]
        certify.solve_batch([cap] * lanes, [cap] * lanes)
    return {"engine": engine, "run": run, "searches": []}


def prepare(state: dict, k: int):
    seed = _pool_seed(state["run"].wl["search"]["pool"], k)
    return seed, {"search_seed": seed}


@contextlib.contextmanager
def recording(executes: list):
    """Keep ``(solver, plan, solved)`` of every ``BatchPlan.execute``."""
    from repro.core.plan import BatchPlan
    orig = BatchPlan.execute

    def execute(self, solver="dual", **kw):
        solved = orig(self, solver, **kw)
        executes.append((solver, self, solved))
        return solved

    BatchPlan.execute = execute
    try:
        yield
    finally:
        BatchPlan.execute = orig


def _lanes(plan, solved, bracket: bool) -> list[dict]:
    out = []
    for cap, dem, s in zip(plan.caps, plan.dems, solved):
        lane = {"cap": cap, "dem": dem, "ub": s.value,
                "iterations": s.iterations}
        if bracket:
            lane.update(lb=s.value, ub=s.meta["ub"])
        out.append(lane)
    return out


def search(state: dict, seed: int) -> dict:
    """One ``optimize`` call; its result with the ranking and the
    certification lanes."""
    from repro import design
    run = state["run"]
    s = run.wl["search"]
    executes: list = []
    with recording(executes):
        result = design.optimize(
            _space(run), moves=tuple(s["moves"]), fleet=s["fleet"],
            runs=s["runs"], elite=s["elite"], rounds=s["rounds"], seed=seed,
            engine=state["engine"])
    ranks = [_lanes(p, solved, False) for solver, p, solved in executes
             if solver != "primal"]
    certs = [_lanes(p, solved, True) for solver, p, solved in executes
             if solver == "primal"]
    return {"result": result, "ranks": ranks,
            "cert": [x for c in certs for x in c]}


def call(state: dict, seed: int):
    found = search(state, seed)
    state["searches"].append(found)
    return found["cert"], {
        "best": gen.digest(found["result"].best.cand.topo.cap),
        "ranking_lanes": sum(len(r) for r in found["ranks"])}


# -- plain checks of what the designer promises ------------------------------

def recipe_fault(attached: np.ndarray, eq: dict) -> bool:
    """Whether a recipe's attached capacity per switch (ToRs first, then
    aggregation, then intermediate switches) breaks the equipment: each
    ToR has its two uplinks, no other switch uses more ports than it has,
    and at most one port is left idle in all (an odd port count)."""
    unit = eq["fabric_gbps"] / eq["server_gbps"]
    n_tor = eq["n_tor"]
    ports = np.concatenate([np.full(eq["d_i"], eq["d_a"]),
                            np.full(eq["d_a"] // 2, eq["d_i"])]) * unit
    return not (len(attached) == n_tor + len(ports)
                and np.all(attached[:n_tor] == 2 * unit)
                and np.all(attached[n_tor:] <= ports)
                and (ports - attached[n_tor:]).sum() <= unit)


def equipment_faults(found: dict, eq: dict) -> int:
    """Lanes whose wiring is not the recipe's equipment: not a symmetric
    loop-free matrix of whole 10 GbE links, a ToR-ToR link, or a switch
    whose attached capacity differs from the recipe's (candidate 0 of the
    first ranking execute); one more when the recipe breaks the
    equipment (``recipe_fault``)."""
    unit = eq["fabric_gbps"] / eq["server_gbps"]
    n_tor = eq["n_tor"]
    lanes = [x for r in found["ranks"] for x in r] + found["cert"]
    recipe = np.asarray(found["ranks"][0][0]["cap"], np.float64).sum(axis=0)
    bad = int(recipe_fault(recipe, eq))
    for lane in lanes:
        cap = np.asarray(lane["cap"], np.float64)
        ok = (np.array_equal(cap, cap.T) and not np.diag(cap).any()
              and cap.min() >= 0
              and np.array_equal(cap / unit, np.round(cap / unit))
              and not cap[:n_tor, :n_tor].any()
              and np.array_equal(cap.sum(axis=0), recipe))
        bad += not ok
    return bad


def selection_faults(found: dict, search: dict) -> int:
    """Where the result disagrees with its selection redone plainly from
    the lanes: each round keeps the top ``elite`` by min-over-samples
    ranking bound (the earlier on ties, previous elites first); the recipe
    is candidate 0 of the first execute; the certified candidates are the
    elites and the recipe unless it is one; each one's lb and ub are the
    min over its samples; best is the first of them with the largest lb.
    Counts elites, certified lanes and best that differ."""
    runs, keep = search["runs"], search["elite"]
    result = found["result"]

    def fleet(lanes):
        return [(min(x["ub"] for x in lanes[i:i + runs]), lanes[i]["cap"])
                for i in range(0, len(lanes), runs)]

    elites = []
    for r, lanes in enumerate(found["ranks"]):
        scored = [(score, cap, (r, i))
                  for i, (score, cap) in enumerate(fleet(lanes))]
        elites = sorted(elites + scored, key=lambda e: -e[0])[:keep]
    reference = (None, found["ranks"][0][0]["cap"], (0, 0))
    if not any(e[2] == reference[2] for e in elites):
        certified = elites + [reference]
    else:
        certified = list(elites)
    cert = found["cert"]
    bad = abs(len(cert) - runs * len(certified))
    bad += abs(len(result.state.elites) - len(elites))
    bad += sum(not np.array_equal(np.asarray(e.cand.topo.cap, np.float32),
                                  cap)
               for e, (_, cap, _) in zip(result.state.elites, elites))
    lbs = []
    for j, (_, cap, _) in enumerate(certified):
        lanes = cert[j * runs:(j + 1) * runs]
        bad += sum(not np.array_equal(x["cap"], cap) for x in lanes)
        lbs.append(min((x["lb"] for x in lanes), default=-1.0))
    best = int(np.argmax(lbs))
    recipe = next(j for j, e in enumerate(certified) if e[2] == reference[2])
    bad += not np.array_equal(
        np.asarray(result.best.cand.topo.cap, np.float32), certified[best][1])
    bad += result.best.lb != lbs[best]
    bad += result.reference.lb != lbs[recipe]
    return int(bad)


def rank_sample(run, searches: list) -> list[dict]:
    """The ranking lanes the reference recomputes, drawn from the seed."""
    lanes = [x for f in searches for r in f["ranks"] for x in r]
    k = min(len(lanes), run.wl["reference"]["rank_lanes"])
    pick = gen.rng_for(run.seed, RANK_SAMPLE_KEY).choice(len(lanes), k,
                                                         replace=False)
    return [lanes[i] for i in sorted(int(i) for i in pick)]


def rank_numbers(run, picked: list[dict]) -> dict[str, float]:
    """Ranking lanes against the plain references, over those with
    theta* > 0: ``rank_ub_rel_diff``, the widest relative gap between a
    lane's upper bound and ``bench/reference_dual.py``'s (the same descent
    and stopping rule); ``rank_ub_under_ref_lb``, how far a lane's upper
    bound lies below ``bench/reference.py``'s lower bound, relative to it
    (0 when above): a certified upper bound can never lie below a
    certified lower bound."""
    from bench import reference_dual
    picked = [x for x in picked if compare.routable(x)]
    if not picked:
        return {"rank_ub_rel_diff": 0.0, "rank_ub_under_ref_lb": 0.0}
    s = run.solver
    ub = np.asarray([x["ub"] for x in picked], np.float64)
    ref_ub = reference_dual.uppers(
        [x["cap"] for x in picked], [x["dem"] for x in picked],
        iters=s["iters"], lr=s["lr"], tol=s["tol"],
        check_every=s["check_every"], block=run.wl["reference"]["block"])
    ref_lb = compare.reference_brackets(run, picked)[:, 0]
    return {"rank_ub_rel_diff": float((np.abs(ub - ref_ub) / ref_ub).max()),
            "rank_ub_under_ref_lb": float(
                np.maximum(0.0, (ref_lb - ub) / ref_lb).max())}


def check(state: dict, run) -> dict:
    """``bad_instances`` (``equipment_faults``), ``selection_mismatch``
    (``selection_faults``), ``below_recipe`` (searches whose best lb is
    below the recipe's) and ``rank_numbers`` over a sample of the
    window's ranking lanes."""
    limits = run.wl["limits"]
    eq, search_kw = run.cfg["equipment"], run.wl["search"]
    found = state["searches"]
    values = {
        "bad_instances": sum(equipment_faults(f, eq) for f in found),
        "selection_mismatch": sum(selection_faults(f, search_kw)
                                  for f in found),
        "below_recipe": sum(f["result"].best.lb < f["result"].reference.lb
                            for f in found),
        **rank_numbers(run, rank_sample(run, found)),
    }
    return {k: {"value": v, "limit": limits[k]} for k, v in values.items()}

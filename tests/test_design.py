"""Designer tests: move-kernel feasibility invariants, seeded determinism
and resume, the designed-vs-recipe non-regression on a tiny VL2 spec, and
the one-BatchPlan-execute-per-round contract; the vl2-design benchmark
cell's entry (``bench/entries/design_optimize.py``) against the plain
reference, its plain checks, and the ``design.*`` spans."""
import dataclasses

import numpy as np
import pytest

from repro.core import heterogeneous as het, vl2
from repro.core.engine import DualEngine
from repro.core.plan import BatchPlan
from repro.design import (MOVES, TwoClassSpace, VL2Space, move_servers,
                          optimize, perturb_bias, swap_edges)

VSPEC = vl2.VL2Spec(d_a=4, d_i=4, servers_per_tor=4)
# 3 + 7 = 10 switches — the same node count as the tiny VL2 space above, so
# (with matching fleet x runs lane counts) every search in this module
# reuses ONE compiled dual program and ONE compiled primal program
TSPEC = het.TwoClassSpec(n_large=3, k_large=12, n_small=7, k_small=5,
                         num_servers=25)


def _cheap_engine():
    return DualEngine(iters=40, tol=1e-3)


@pytest.fixture(scope="module")
def vl2_result():
    """One shared tiny VL2 search (determinism re-runs it below)."""
    return optimize(VL2Space(VSPEC, VSPEC.n_tor_full),
                    engine=_cheap_engine(), moves=("swap",), rounds=2,
                    fleet=4, elite=2, runs=2, seed=0)


# --- move kernels -----------------------------------------------------------

def _check_same_equipment(old, new):
    """A move may rewire links but never mint ports, capacity or servers."""
    assert np.allclose(new.cap, new.cap.T)
    assert np.all(np.diag(new.cap) == 0)
    assert np.all(new.cap >= 0)
    assert np.allclose(new.cap.sum(axis=0), old.cap.sum(axis=0)), \
        "per-switch attached capacity (ports x line speed) must be preserved"
    assert int(new.servers.sum()) == int(old.servers.sum())


@pytest.mark.parametrize("seed", range(5))
def test_swap_preserves_degrees_and_forbidden_pairs(seed):
    space = VL2Space(VSPEC, VSPEC.n_tor_full)
    cand = space.initial(seed)
    new = swap_edges(cand, np.random.default_rng(seed), space)
    assert new is not None and new.origin == "swap"
    _check_same_equipment(cand.topo, new.topo)
    assert not np.array_equal(new.topo.cap, cand.topo.cap), \
        "a successful swap must change the wiring"
    tor = new.topo.labels == 0
    assert np.all(new.topo.cap[np.ix_(tor, tor)] == 0), \
        "VL2 swaps must never create ToR-ToR links"


@pytest.mark.parametrize("seed", range(3))
def test_parametric_moves_rebuild_feasible_topologies(seed):
    space = TwoClassSpace(TSPEC)
    cand = space.initial(seed)
    rng = np.random.default_rng(seed)
    moved = move_servers(cand, rng, space)
    assert moved is not None and moved.origin == "servers"
    assert int(moved.topo.servers.sum()) == TSPEC.num_servers
    lo, hi = space.param_bounds["servers_on_large"]
    assert lo <= moved.params["servers_on_large"] <= hi
    moved.topo.validate()

    biased = perturb_bias(cand, rng, space)
    assert biased is not None and biased.origin == "bias"
    lo, hi = space.param_bounds["cross_bias"]
    assert lo <= biased.params["cross_bias"] <= hi
    biased.topo.validate()


def test_parametric_moves_skip_nonparametric_spaces():
    space = VL2Space(VSPEC, VSPEC.n_tor_full)
    cand = space.initial(0)
    rng = np.random.default_rng(0)
    assert move_servers(cand, rng, space) is None
    assert perturb_bias(cand, rng, space) is None
    assert set(MOVES) == {"swap", "servers", "bias"}


# --- optimizer --------------------------------------------------------------

def test_seeded_determinism(vl2_result):
    again = optimize(VL2Space(VSPEC, VSPEC.n_tor_full),
                     engine=_cheap_engine(), moves=("swap",), rounds=2,
                     fleet=4, elite=2, runs=2, seed=0)
    assert [e.score for e in again.elites] == \
        [e.score for e in vl2_result.elites]
    assert [e.lb for e in again.elites] == [e.lb for e in vl2_result.elites]
    for a, b in zip(again.elites, vl2_result.elites):
        assert np.array_equal(a.cand.topo.cap, b.cand.topo.cap)
    assert again.history == vl2_result.history


def test_resume_matches_uninterrupted(vl2_result):
    first = optimize(VL2Space(VSPEC, VSPEC.n_tor_full),
                     engine=_cheap_engine(), moves=("swap",), rounds=1,
                     fleet=4, elite=2, runs=2, seed=0)
    resumed = optimize(VL2Space(VSPEC, VSPEC.n_tor_full),
                       engine=_cheap_engine(), moves=("swap",), rounds=1,
                       fleet=4, elite=2, runs=2, seed=0, state=first.state)
    assert [e.score for e in resumed.elites] == \
        [e.score for e in vl2_result.elites]
    assert resumed.state.rounds_done == 2


@pytest.mark.parametrize("seed", [0, 4])
def test_resume_matches_uninterrupted_with_parametric_moves(seed):
    """Resume must pair the rng stream with the same elite parents as an
    uninterrupted run even when the certified-lb ordering disagrees with
    the search-score ordering (seed 4 used to diverge: the state stored
    lb-sorted elites while the loop ranked by dual score)."""
    kw = dict(engine=_cheap_engine(), rounds=1, fleet=4, elite=2, runs=2,
              seed=seed)
    straight = optimize(TwoClassSpace(TSPEC), rounds=2, **{
        k: v for k, v in kw.items() if k != "rounds"})
    first = optimize(TwoClassSpace(TSPEC), **kw)
    resumed = optimize(TwoClassSpace(TSPEC), state=first.state, **kw)
    assert resumed.history == straight.history[-1:]
    assert [e.score for e in resumed.state.elites] == \
        [e.score for e in straight.state.elites]
    for a, b in zip(resumed.state.elites, straight.state.elites):
        assert np.array_equal(a.cand.topo.cap, b.cand.topo.cap)


def test_designed_vl2_never_below_recipe(vl2_result):
    """The acceptance criterion: the optimizer's certified lower bound is
    >= the hand-coded ``rewired_vl2_topology`` recipe's certified bound
    (the recipe is candidate 0 and stays in the final certification)."""
    assert vl2_result.best.lb is not None
    assert vl2_result.best.lb >= vl2_result.reference.lb
    assert vl2_result.best.lb <= vl2_result.best.ub
    # the reference really is the recipe wiring
    recipe = vl2.rewired_vl2_topology(VSPEC, VSPEC.n_tor_full, seed=0)
    assert np.array_equal(vl2_result.reference.cand.topo.cap, recipe.cap)


def test_one_execute_per_round_and_shared_compile_keys(vl2_result):
    s = vl2_result.stats
    # init eval + one execute per round; exactly one certification pass
    assert s["search_executes"] == 1 + s["rounds"] == 3
    assert s["certify_executes"] == 1
    assert s["executes"] == 4
    # same-size candidates share compile keys: one (padded_n, lanes) shape
    # for every search round + one for the (elite+1)-lane certify pass
    assert len(s["compile_keys"]) == 2
    assert s["last_plan"]["instances"] == 4 * 2   # fleet x runs


def test_optimizer_rejects_bad_inputs():
    space = VL2Space(VSPEC, VSPEC.n_tor_full)
    with pytest.raises(ValueError, match="unknown move"):
        optimize(space, moves=("warp",), rounds=0)
    with pytest.raises(ValueError, match="BatchPlan"):
        optimize(space, engine="exact", rounds=0)
    with pytest.raises(ValueError, match="fleet"):
        optimize(space, fleet=0)


def test_two_class_search_improves_or_matches_recipe():
    res = optimize(TwoClassSpace(TSPEC), engine=_cheap_engine(),
                   rounds=1, fleet=4, elite=2, runs=2, seed=1)
    assert res.best.lb >= res.reference.lb
    assert res.reference.cand.params["cross_bias"] == 1.0


# --- plan refill (the round-to-round fast path) -----------------------------

def test_plan_refill_reuses_structure_and_checks_shapes():
    topos = [vl2.rewired_vl2_topology(VSPEC, VSPEC.n_tor_full, s)
             for s in range(3)]
    dems = [np.ones((t.n, t.n)) - np.eye(t.n) for t in topos]
    plan = BatchPlan.build(topos, dems, devices=1)
    refilled = plan.refill(list(reversed(topos)), dems)
    assert refilled.chunks is plan.chunks
    assert refilled.stats.compile_keys == plan.stats.compile_keys
    with pytest.raises(ValueError, match="refill needs"):
        plan.refill(topos[:2], dems[:2])
    small = vl2.vl2_topology(vl2.VL2Spec(d_a=2, d_i=2))
    with pytest.raises(ValueError, match="nodes"):
        plan.refill([small] * 3, dems)


# --- the vl2-design cell's entry and its plain checks -----------------------

CELL_SPEC = {"d_a": 6, "d_i": 6, "n_tor": 12}
CELL_SEARCH = {"fleet": 6, "runs": 2, "elite": 2, "rounds": 2}


def _cell_run(seed=3):
    """The cell's own configuration and workload (``bench/``) at VL2Spec(6,
    6) with 12 ToRs, a fleet of 6 and 100 iterations."""
    from bench.files import BENCH, load_json
    from bench.run import Run
    cfg = load_json(BENCH / "configs" / "vl2-da22.json")
    wl = load_json(BENCH / "workloads" / "vl2-design.json")
    cfg["equipment"] = {**cfg["equipment"], **CELL_SPEC}
    cfg["solver"] = {**cfg["solver"], "iters": 100}
    wl["search"] = {**wl["search"], **CELL_SEARCH}
    return Run("vl2-design", wl, cfg, seed, 0.0, False, {})


@pytest.fixture(scope="module")
def cell():
    """One search through the cell's entry, with the spans it recorded."""
    from bench.files import load_module
    from repro.core import get_engine, spans
    entry = load_module("entries", "design_optimize")
    run = _cell_run()
    state = {"engine": get_engine(run.solver["engine"],
                                  **run.engine_kwargs()),
             "run": run, "searches": []}
    spans.clear()
    found = entry.search(state, seed=11)
    return {"entry": entry, "run": run, "found": found,
            "spans": spans.records()}


def test_cell_certification_agrees_with_reference(cell):
    from bench import compare
    run, cert = cell["run"], cell["found"]["cert"]
    assert len(cert) in (2 * 2, 3 * 2)       # (elite [+ recipe]) x runs
    ref = compare.reference_brackets(run, cert)
    got = compare.numbers([x["lb"] for x in cert], [x["ub"] for x in cert],
                          ref)
    limits = run.wl["limits"]
    for name, per_lane in got.items():
        assert per_lane.max() <= limits[name], name
    assert all(0 < x["lb"] <= x["ub"] and 0 < x["iterations"] <= 100
               for x in cert)


def test_cell_ranking_bounds_agree_with_reference(cell):
    entry, run, found = cell["entry"], cell["run"], cell["found"]
    lanes = [x for r in found["ranks"] for x in r]
    assert len(lanes) == 3 * 6 * 2          # (1 + rounds) x fleet x runs
    got = entry.rank_numbers(run, lanes[::3])
    for name, value in got.items():
        assert value <= run.wl["limits"][name], name


def _altered(found, lane_fn):
    """A copy of a search whose last ranking execute's first lane is
    ``lane_fn(lane)``."""
    ranks = [list(r) for r in found["ranks"]]
    ranks[-1][0] = lane_fn(dict(ranks[-1][0]))
    return {**found, "ranks": ranks}


def _extra_link(lane, u, v):
    cap = lane["cap"].copy()
    cap[u, v] += vl2.FABRIC
    cap[v, u] += vl2.FABRIC
    return {**lane, "cap": cap}


@pytest.mark.parametrize("fault,want", [
    (None, 0),
    ("capacity", 1),     # one more link between an agg and a core switch
    ("tor_link", 1),     # two ToRs wired to each other
])
def test_cell_equipment_check(cell, fault, want):
    entry, found = cell["entry"], cell["found"]
    eq = {**cell["run"].cfg["equipment"]}
    n_tor = eq["n_tor"]
    if fault == "capacity":
        found = _altered(found, lambda x: _extra_link(x, n_tor, n_tor + 6))
    elif fault == "tor_link":
        found = _altered(found, lambda x: _extra_link(x, 0, 1))
    assert entry.equipment_faults(found, eq) == want


def test_cell_selection_redone_plainly_matches_result(cell):
    entry, found = cell["entry"], cell["found"]
    search = cell["run"].wl["search"]
    result = found["result"]
    assert entry.selection_faults(found, search) == 0
    assert result.best.lb >= result.reference.lb
    # a best that is not the argmax of the certified lower bounds
    others = [e for e in result.elites + [result.reference]
              if e.lb < result.best.lb]
    if others:
        wrong = dataclasses.replace(result, best=others[0])
        assert entry.selection_faults({**found, "result": wrong},
                                      search) > 0


def test_cell_records_design_spans(cell):
    recs, found = cell["spans"], cell["found"]
    by_name = {}
    for r in recs:
        by_name.setdefault(r.name, []).append(r)
    (root,) = by_name["design.optimize"]
    assert root.counts == {"rounds": 2, "fleet": 6, "runs": 2}
    propose = by_name["design.propose"]
    assert [r.counts for r in propose] == \
        [{"proposals": 6, "restarts": 0}] * 2
    rank = by_name["design.rank"]
    assert [r.counts["refilled"] for r in rank] == [0, 1, 1]
    for r, lanes in zip(rank, found["ranks"]):
        assert r.counts["lanes"] == len(lanes) == 12
        assert r.counts["lane_iters_used"] == \
            sum(x["iterations"] for x in lanes)
        # one chunk: every lane runs as long as the longest
        assert r.counts["lane_iters_run"] == \
            len(lanes) * max(x["iterations"] for x in lanes)
    (cert,) = by_name["design.certify"]
    assert cert.counts["lanes"] == len(found["cert"])
    assert cert.counts["lane_iters_used"] == \
        sum(x["iterations"] for x in found["cert"])
    design = {r.id for r in recs if r.name in ("design.rank",
                                               "design.certify")}
    plan_spans = [r for r in recs if r.name.startswith("plan.")]
    assert plan_spans and all(r.root == root.id for r in recs)
    assert {r.parent for r in plan_spans if r.name == "plan.sync"} <= design


def test_rank_lane_iterations_counted_by_chunk():
    """On a plan of two chunks each lane runs as long as its chunk's
    longest lane: the spans' counts against a count by hand over the
    plan's chunks."""
    from bench.files import load_module
    from repro.core import spans
    from repro.design.optimizer import _lane_iters
    from repro.core.plan import InstanceSolve
    executes = []
    spans.clear()
    with load_module("entries", "design_optimize").recording(executes):
        optimize(VL2Space(VSPEC, VSPEC.n_tor_full),
                 engine=DualEngine(iters=60, tol=1e-2, max_lanes=4,
                                   devices=1),
                 moves=("swap",), rounds=0, fleet=4, elite=2, runs=2, seed=5)
    recs = [r for r in spans.records()
            if r.name in ("design.rank", "design.certify")]
    assert [r.name for r in recs] == ["design.rank", "design.certify"]
    assert len(executes[0][1].chunks) == 2       # 8 ranking lanes, 4 a chunk
    for r, (_, plan, solved) in zip(recs, executes):
        its = [s.iterations for s in solved]
        assert r.counts["lanes"] == len(solved)
        assert r.counts["lane_iters_used"] == sum(its)
        assert r.counts["lane_iters_run"] == sum(
            len(c.indices) * max(its[i] for i in c.indices)
            for c in plan.chunks)

    def lane(iterations, chunk):
        return InstanceSolve(0.0, iterations, {"chunk": chunk})

    hand = [lane(25, 0), lane(50, 0), lane(50, 0), lane(75, 1), lane(25, 1)]
    assert _lane_iters(hand) == {"lanes": 5, "lane_iters_used": 225,
                                 "lane_iters_run": 3 * 50 + 2 * 75}

"""Primal Frank–Wolfe solver + certified brackets (PR 4).

Covers: lower-bound correctness vs the exact LP, the free dual upper bound,
padded batching == per-instance solves through the ``BatchPlan`` primal
path, early stopping, unroutable demand, the PrimalEngine/CertifiedEngine
result contracts, and bracket aggregation in ``run_sweeps``.
"""
import numpy as np
import pytest

from repro.core import graphs, lp, mcf, primal, traffic
from repro.core.engine import (CertifiedEngine, DualEngine, PrimalEngine,
                               Sweep, get_engine, run_sweep)
from repro.core.plan import BatchPlan


def _instance(n, seed, r=4, servers=3):
    topo = graphs.random_regular_graph(n, r, seed, servers=servers)
    dem = traffic.make("permutation", topo.servers, seed + 1)
    return topo, dem


# ---------------------------------------------------------------------------
# solver core
# ---------------------------------------------------------------------------

def test_primal_brackets_the_exact_optimum():
    topo, dem = _instance(16, 0)
    exact = lp.max_concurrent_flow(topo, dem, want_flows=False).throughput
    res = primal.solve_primal(topo, dem, iters=500)
    assert res.throughput_lb <= exact * (1 + 1e-4), \
        "primal iterate must lower-bound the optimum"
    assert exact <= res.throughput_ub * (1 + 1e-4), \
        "the riding dual bound must upper-bound it"
    assert res.throughput_lb >= exact * 0.94, "and converge within ~6%"
    assert res.gap == pytest.approx(
        (res.throughput_ub - res.throughput_lb) / res.throughput_ub)
    assert res.iterations == 500
    assert res.final_util > 0


def test_primal_ub_matches_mcf_dual():
    # the fused loop's dual descent is the same trajectory mcf runs
    topo, dem = _instance(14, 3)
    fused = primal.solve_primal(topo, dem, iters=400)
    dual = mcf.solve_dual(topo, dem, iters=400)
    assert fused.throughput_ub == pytest.approx(dual.throughput_ub, rel=5e-3)


def test_primal_padded_batch_matches_single():
    topo, dem = _instance(16, 0)
    ref = primal.solve_primal(topo, dem, iters=300)
    capp = np.zeros((1, 32, 32), np.float32)
    demp = np.zeros((1, 32, 32), np.float32)
    capp[0, :16, :16] = topo.cap
    demp[0, :16, :16] = dem
    res = primal.solve_primal_batch(capp, demp, n_valid=np.array([16]),
                                    iters=300)
    # node padding reorders float reductions, which can flip individual
    # line-search bisections: the FW trajectory (and so the lb) matches to
    # a few 1e-3, the dual ub more tightly
    assert res.throughput_lb[0] == pytest.approx(ref.throughput_lb, rel=5e-3)
    assert res.throughput_ub[0] == pytest.approx(ref.throughput_ub, rel=1e-3)
    assert res.iterations[0] == 300


def test_primal_early_stop_keeps_certification():
    topo, dem = _instance(16, 5)
    full = primal.solve_primal(topo, dem, iters=1500)
    early = primal.solve_primal(topo, dem, iters=1500, tol=1e-4)
    assert early.iterations < 1500, "tolerance reached => early exit"
    assert early.iterations % 25 == 0, "stops on a check boundary"
    # both are certified: early lb below full lb (less averaging), both
    # below the ub
    assert early.throughput_lb <= full.throughput_lb * (1 + 1e-5)
    assert early.throughput_lb <= early.throughput_ub
    assert early.throughput_lb == pytest.approx(full.throughput_lb,
                                                rel=0.05)


def test_primal_tol_zero_never_stops_early():
    topo, dem = _instance(12, 7)
    res = primal.solve_primal(topo, dem, iters=120, tol=0.0)
    assert res.iterations == 120


def test_primal_unroutable_demand_reports_zero_lb():
    cap = np.zeros((4, 4))
    cap[0, 1] = cap[1, 0] = cap[2, 3] = cap[3, 2] = 1.0
    dem = np.zeros((4, 4))
    dem[0, 1] = 1.0
    dem[0, 2] = 1.0    # demand across disconnected components
    res = primal.solve_primal(cap, dem, iters=50)
    assert res.throughput_lb == 0.0, "no feasible flow routes all demand"
    assert res.throughput_ub < 1e-6, "dual agrees theta* = 0"


# ---------------------------------------------------------------------------
# BatchPlan primal path
# ---------------------------------------------------------------------------

def test_plan_primal_solver_matches_per_instance():
    insts = [_instance(n, s) for s, n in enumerate([12, 14, 16, 20])]
    topos = [t for t, _ in insts]
    dems = [d for _, d in insts]
    plan = BatchPlan.build(topos, dems, bucket="pow2", devices=1)
    out = plan.execute(solver="primal", iters=300)
    for (topo, dem), got in zip(insts, out):
        ref = primal.solve_primal(topo, dem, iters=300)
        assert got.value == pytest.approx(ref.throughput_lb, rel=1e-3)
        assert got.meta["ub"] == pytest.approx(ref.throughput_ub, rel=1e-3)
        assert got.meta["final_util"] == pytest.approx(ref.final_util,
                                                       rel=1e-3)


def test_plan_unknown_solver_raises():
    topo, dem = _instance(12, 0)
    plan = BatchPlan.build([topo], [dem], devices=1)
    with pytest.raises(ValueError, match="unknown plan solver"):
        plan.execute(solver="simplex", iters=10)


def test_primal_plan_reuses_dual_plan_shapes():
    # primal lanes ride the same buckets/chunks/sharding: identical plans
    insts = [_instance(n, s) for s, n in enumerate([12, 16, 16, 20, 24])]
    topos = [t for t, _ in insts]
    dems = [d for _, d in insts]
    dual_eng = DualEngine(iters=50, devices=1, max_lanes=2)
    prim_eng = PrimalEngine(iters=50, devices=1, max_lanes=2)
    assert dual_eng.plan(topos, dems).stats.compile_keys == \
        prim_eng.plan(topos, dems).stats.compile_keys
    prim_eng.solve_batch(topos, dems)
    assert prim_eng.last_plan.compile_keys == \
        dual_eng.plan(topos, dems).stats.compile_keys


# ---------------------------------------------------------------------------
# engines
# ---------------------------------------------------------------------------

def test_primal_engine_result_contract():
    topo, dem = _instance(16, 2)
    eng = get_engine("primal", iters=200)
    single = eng.solve(topo, dem)
    assert single.engine == "primal" and single.bound == "lower"
    assert not single.is_upper_bound
    assert set(single.meta) == {"iterations", "final_util", "ub"}
    [batched] = eng.solve_batch([topo], [dem])
    assert batched.throughput == pytest.approx(single.throughput, rel=1e-3)
    assert batched.bound == "lower"
    assert {"iterations", "final_util", "ub", "bucket", "chunk",
            "plan"} <= set(batched.meta)


def test_certified_engine_bracket_contract():
    insts = [_instance(n, s) for s, n in enumerate([12, 16])]
    eng = get_engine("certified", iters=200)
    out = eng.solve_batch([t for t, _ in insts], [d for _, d in insts])
    for (topo, dem), got in zip(insts, out):
        assert got.engine == "certified" and got.bound == "bracket"
        assert got.is_upper_bound and got.throughput == got.meta["ub"]
        assert 0 <= got.meta["lb"] <= got.meta["ub"]
        assert got.meta["gap"] == pytest.approx(
            (got.meta["ub"] - got.meta["lb"]) / got.meta["ub"])
        single = eng.solve(topo, dem)
        assert single.bound == "bracket"
        assert single.meta["lb"] == pytest.approx(got.meta["lb"], rel=1e-3)
        assert single.meta["ub"] == pytest.approx(got.meta["ub"], rel=1e-3)


def test_dual_engine_meta_unchanged_by_refactor():
    # the planned-engine refactor must not leak primal keys into dual meta
    topo, dem = _instance(12, 1)
    eng = DualEngine(iters=100)
    [got] = eng.solve_batch([topo], [dem])
    assert set(got.meta) == {"iterations", "final_ratio", "batch_size",
                             "bucket", "padded_n", "nodes", "chunk",
                             "chunks", "devices", "plan"}
    assert got.bound == "upper"


def test_certified_engine_registry_kwargs():
    eng = get_engine("certified", iters=30, bucket=None, devices=1,
                     max_lanes=4)
    assert isinstance(eng, CertifiedEngine)
    assert eng.bucket is None and eng.max_lanes == 4
    with pytest.raises(ValueError, match="bucket mode"):
        get_engine("certified", bucket="fib")


# ---------------------------------------------------------------------------
# sweep bracket aggregation
# ---------------------------------------------------------------------------

def test_run_sweep_aggregates_brackets():
    def build(x, seed):
        return graphs.random_regular_graph(12, 4, seed, servers=3)

    sweep = Sweep(xs=(0.0, 1.0), runs=2)
    pts = run_sweep(sweep, build, engine=get_engine("certified", iters=100))
    for p in pts:
        assert p.lb_mean is not None and p.gap_max is not None
        assert p.lb_mean <= p.mean * (1 + 1e-6)
        assert 0 <= p.gap_max < 1
    # non-bracket engines leave the fields None
    pts = run_sweep(sweep, build, engine=get_engine("dual", iters=100))
    assert all(p.lb_mean is None and p.gap_max is None for p in pts)

"""Size-bucketed padded batching + early-stopping dual engine (PR 2).

Covers: bucket-size selection, padded-vs-unpadded equivalence on mixed-size
batches, convergence-based early stopping (single and batched), per-instance
batch meta, and the interpret-mode auto-detection plumbing.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import graphs, mcf, plan, primal, routing, traffic
from repro.core.engine import DualEngine, bucket_size
from repro.kernels import ops


def _instance(n, seed, r=4):
    topo = graphs.random_regular_graph(n, r, seed, servers=3)
    dem = traffic.make("permutation", topo.servers, seed + 1)
    return topo, dem


# ---------------------------------------------------------------------------
# bucket sizing
# ---------------------------------------------------------------------------

def test_bucket_size_modes():
    assert [bucket_size(n, "pow2") for n in (5, 8, 9, 40, 64, 65)] == \
        [8, 8, 16, 64, 64, 128]
    assert bucket_size(40, "mult128") == 128
    assert bucket_size(129, "mult128") == 256
    assert bucket_size(40, 32) == 64
    assert bucket_size(40, None) == 40
    assert bucket_size(40, "none") == 40
    with pytest.raises(ValueError, match="bucket mode"):
        bucket_size(40, "fib")
    with pytest.raises(ValueError, match="bucket mode"):
        DualEngine(bucket="fib")   # engine fails fast at construction


# ---------------------------------------------------------------------------
# padded batching == per-instance solves
# ---------------------------------------------------------------------------

def test_padded_batch_matches_per_instance_solve_dual():
    insts = [_instance(n, s) for s, n in enumerate([12, 14, 16, 20, 24])]
    eng = DualEngine(iters=300, bucket="pow2")
    out = eng.solve_batch([t for t, _ in insts], [d for _, d in insts])
    buckets = {r.meta["bucket"] for r in out}
    assert buckets == {16, 32}, "12/14/16 -> 16; 20/24 -> 32"
    for (topo, dem), got in zip(insts, out):
        ref = mcf.solve_dual(topo, dem, iters=300)
        assert got.throughput == pytest.approx(ref.throughput_ub, rel=1e-3)
        assert got.meta["nodes"] == topo.n


def test_padded_solve_dual_batch_masks_padding():
    topo, dem = _instance(16, 0)
    ref = mcf.solve_dual(topo, dem, iters=300)
    capp = np.zeros((1, 32, 32), np.float32)
    demp = np.zeros((1, 32, 32), np.float32)
    capp[0, :16, :16] = topo.cap
    demp[0, :16, :16] = dem
    res = mcf.solve_dual_batch(capp, demp, n_valid=np.array([16]), iters=300)
    assert res.throughput_ub[0] == pytest.approx(ref.throughput_ub, rel=1e-3)
    assert res.iterations[0] == 300
    assert np.isfinite(res.final_ratio[0])


# ---------------------------------------------------------------------------
# early stopping
# ---------------------------------------------------------------------------

def test_early_stop_fewer_iters_same_bound():
    topo, dem = _instance(16, 3)
    full = mcf.solve_dual(topo, dem, iters=2000)
    assert full.iterations == 2000
    tol = 1e-4
    early = mcf.solve_dual(topo, dem, iters=2000, tol=tol)
    assert early.iterations < 2000, "tolerance reached => early exit"
    assert early.iterations % 25 == 0, "stops on a check boundary"
    # certified bound unchanged within a few windows' worth of tolerance
    # (the window depends on how the SP-DAG adjoint splits ties, so the
    # margin is loose; see repro.core.apsp)
    assert early.throughput_ub == pytest.approx(full.throughput_ub, rel=0.03)
    assert early.throughput_ub >= full.throughput_ub - 1e-6, \
        "early bound is still an upper bound on the converged one"


def test_batch_early_stop_is_per_instance():
    insts = [_instance(n, s) for s, n in enumerate([12, 16, 16, 20])]
    eng = DualEngine(iters=1500, tol=1e-4, bucket="pow2")
    out = eng.solve_batch([t for t, _ in insts], [d for _, d in insts])
    its = [r.meta["iterations"] for r in out]
    assert all(i < 1500 for i in its)
    assert len(set(its)) > 1, "lanes converge at different iterations"
    for (topo, dem), got in zip(insts, out):
        # same tolerance per-instance solve: padding must not change when or
        # where a lane stops (modulo float noise)
        same = mcf.solve_dual(topo, dem, iters=1500, tol=1e-4)
        assert got.throughput == pytest.approx(same.throughput_ub, rel=5e-3)
        # still a certified bound, within a couple percent of the full run
        full = mcf.solve_dual(topo, dem, iters=1500)
        assert got.throughput >= full.throughput_ub - 1e-6
        assert got.throughput == pytest.approx(full.throughput_ub, rel=0.025)


def test_tol_zero_never_stops_early():
    topo, dem = _instance(12, 7)
    res = mcf.solve_dual(topo, dem, iters=120, tol=0.0)
    assert res.iterations == 120


# ---------------------------------------------------------------------------
# batch meta (satellite: solve_batch used to report the cap + drop ratio)
# ---------------------------------------------------------------------------

def test_solve_batch_meta_matches_solver_outputs():
    insts = [_instance(n, s) for s, n in enumerate([12, 16])]
    eng = DualEngine(iters=200, bucket="pow2")
    out = eng.solve_batch([t for t, _ in insts], [d for _, d in insts])
    for (topo, dem), got in zip(insts, out):
        assert set(got.meta) == {"iterations", "final_ratio", "batch_size",
                                 "bucket", "padded_n", "nodes", "chunk",
                                 "chunks", "devices", "plan"}
        assert got.meta["iterations"] == 200
        assert np.isfinite(got.meta["final_ratio"])
        assert got.meta["plan"]["instances"] == 2
        assert got.meta["chunk"] < got.meta["chunks"]
        single = eng.solve(topo, dem)
        assert got.meta["final_ratio"] == pytest.approx(
            single.meta["final_ratio"], rel=1e-3)


_BATCH_ENTRIES = {
    "dual": (mcf.solve_dual_batch, mcf.DualBatchResult),
    "dual-demgrad": (mcf.solve_dual_demgrad_batch,
                     mcf.DualDemgradBatchResult),
    "primal": (primal.solve_primal_batch, primal.PrimalBatchResult),
    "ecmp": (routing.solve_ecmp_batch, routing.RoutingBatchResult),
    "ksp": (routing.solve_ksp_batch, routing.RoutingBatchResult),
}


@pytest.mark.parametrize("solver", sorted(plan.SOLVERS))
def test_empty_batch_returns_empty(solver):
    """Every batch entry: an empty batch gives an empty result of its own
    type (regression: np.stack([]) used to blow up with an opaque error),
    a length mismatch raises, and ``n_valid`` defaults to the full
    width."""
    entry, result_type = _BATCH_ENTRIES[solver]
    empty = entry([], [])
    assert type(empty) is result_type
    assert len(empty) == 0 and empty.iterations.shape == (0,)
    insts = [_instance(8, s, r=3) for s in range(2)]
    caps = [t.cap for t, _ in insts]
    dems = [d for _, d in insts]
    with pytest.raises(ValueError, match="equal length"):
        entry(caps, dems[:1])
    default = entry(caps, dems, iters=30)
    full = entry(caps, dems, n_valid=np.full(2, 8), iters=30)
    assert type(default) is result_type and len(default) == 2
    for a, b in zip(dataclasses.astuple(default), dataclasses.astuple(full)):
        np.testing.assert_array_equal(a, b)
    if solver == "dual":
        assert DualEngine(iters=50).solve_batch([], []) == []


def test_batch_length_mismatch_raises():
    topo, dem = _instance(12, 0)
    with pytest.raises(ValueError, match="equal length"):
        mcf.solve_dual_batch([topo.cap], [])
    with pytest.raises(ValueError, match="equal length"):
        mcf.solve_dual_batch([], [dem])


def test_solve_dual_batch_result_is_sequence_of_bounds():
    caps = np.stack([graphs.random_regular_graph(12, 4, s).cap
                     for s in range(3)])
    dems = np.stack([traffic.make("permutation", np.full(12, 2), s)
                     for s in range(3)])
    res = mcf.solve_dual_batch(caps, dems, iters=100)
    assert len(res) == 3
    assert list(res) == [res[i] for i in range(3)]
    assert res.iterations.shape == (3,)


# ---------------------------------------------------------------------------
# interpret-mode plumbing
# ---------------------------------------------------------------------------

def test_resolve_interpret():
    import jax
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(False) is False
    auto = ops.resolve_interpret(None)
    assert auto == (jax.default_backend() != "tpu")


def test_dual_pallas_interpret_threads_through_engine():
    # explicit interpret=True must work on any backend; squaring-pallas on
    # a small instance exercises the ref fallback inside ops.minplus_matmul
    topo, dem = _instance(16, 1)
    eng = DualEngine(backend="squaring-pallas", interpret=True, iters=150)
    plain = DualEngine(iters=150)
    a = eng.solve(topo, dem)
    b = plain.solve(topo, dem)
    assert a.throughput == pytest.approx(b.throughput, rel=1e-3)
    out = eng.solve_batch([topo], [dem])
    assert out[0].throughput == pytest.approx(a.throughput, rel=1e-3)

"""BatchPlan execution core: bucketing, chunking under a lane budget, and
multi-device sharded dual solves.

The multi-device tests need several XLA devices; CI runs this module as a
dedicated matrix entry with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_plan.py -q

In the plain tier-1 run (one CPU device) those tests skip and the
single-device planning/chunking tests still execute, as does one sharded
solve in a child process with four virtual devices.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import graphs, mcf, traffic
from repro.core.engine import DualEngine
from repro.core.plan import BatchPlan, bucket_size, device_count

NDEV = len(jax.local_devices())
needs_8_devices = pytest.mark.skipif(
    NDEV < 8, reason="run with XLA_FLAGS=--xla_force_host_platform_"
                     "device_count=8 to exercise the sharded path")


def _instances(ns, deg=4, servers=3):
    topos, dems = [], []
    for s, n in enumerate(ns):
        t = graphs.random_regular_graph(n, deg, seed=s, servers=servers)
        topos.append(t)
        dems.append(traffic.make("permutation", t.servers, seed=s + 1))
    return topos, dems


def _bounds(results):
    return np.array([r.throughput for r in results])


# ---------------------------------------------------------------------------
# plan structure (device-count independent)
# ---------------------------------------------------------------------------

def test_plan_buckets_and_padding():
    topos, dems = _instances([12, 14, 16, 20, 24, 33])
    plan = BatchPlan.build(topos, dems, bucket="pow2", devices=1)
    assert plan.stats.instances == 6
    assert plan.stats.buckets == 3          # 16 / 32 / 64
    assert plan.stats.chunks == 3           # no lane budget: one per bucket
    assert plan.stats.lanes_padded == 0     # 1 device: no batch padding
    # members pad to the largest member, not the bucket ceiling
    by_bucket = {c.bucket: c for c in plan.chunks}
    assert by_bucket[16].padded_n == 16
    assert by_bucket[32].padded_n == 24
    assert by_bucket[64].padded_n == 33
    assert set(plan.stats.compile_keys) == {(16, 3), (24, 2), (33, 1)}


def test_plan_chunking_under_lane_budget():
    topos, dems = _instances([16] * 7)
    plan = BatchPlan.build(topos, dems, max_lanes=3, devices=1)
    assert [len(c.indices) for c in plan.chunks] == [3, 3, 1]
    # trailing chunk padded to the shared shape: ONE compile key
    assert all(c.lanes == 3 for c in plan.chunks)
    assert plan.stats.compile_keys == ((16, 3),)
    assert plan.stats.lanes_padded == 2
    # padded lanes replicate a real instance, never a zero instance
    capp, _, n_valid = plan._pack(plan.chunks[-1])
    assert np.array_equal(capp[1], capp[0]) and np.array_equal(capp[2],
                                                               capp[0])
    assert np.all(n_valid == 16)


def test_plan_chunked_results_match_unchunked():
    topos, dems = _instances([12, 14, 16, 20, 24, 33, 40, 40])
    whole = DualEngine(iters=150, devices=1)
    chunked = DualEngine(iters=150, max_lanes=2, devices=1)
    a = _bounds(whole.solve_batch(topos, dems))
    b = _bounds(chunked.solve_batch(topos, dems))
    assert np.array_equal(a, b), "chunking must not change any bound"
    assert chunked.last_plan.chunks > whole.last_plan.chunks


def test_plan_empty():
    plan = BatchPlan.build([], [], devices=1)
    assert plan.chunks == [] and plan.execute(iters=10) == []


def test_plan_rejects_bad_knobs():
    topos, dems = _instances([12])
    with pytest.raises(ValueError, match="max_lanes"):
        BatchPlan.build(topos, dems, max_lanes=0)
    with pytest.raises(ValueError, match="devices"):
        BatchPlan.build(topos, dems, devices=NDEV + 1)
    with pytest.raises(ValueError, match="equal length"):
        BatchPlan.build(topos, [])
    assert device_count(None) == NDEV


def test_engine_meta_reports_plan_placement():
    topos, dems = _instances([12, 16, 16])
    eng = DualEngine(iters=100, max_lanes=2, devices=1)
    out = eng.solve_batch(topos, dems)
    assert [r.meta["chunk"] for r in out] == [0, 0, 1]
    assert all(r.meta["devices"] == 1 for r in out)
    assert out[0].meta["plan"] == eng.last_plan.as_dict()


# ---------------------------------------------------------------------------
# sharded path (8 virtual CPU devices in the CI matrix entry)
# ---------------------------------------------------------------------------

@needs_8_devices
def test_sharded_bounds_bit_identical_to_single_device():
    # 10 mixed-size instances: uneven against 8 devices in every bucket
    topos, dems = _instances([12, 14, 16, 16, 20, 20, 24, 24, 33, 40])
    one = DualEngine(iters=150, devices=1)
    many = DualEngine(iters=150, devices=8)
    a = _bounds(one.solve_batch(topos, dems))
    b = _bounds(many.solve_batch(topos, dems))
    assert np.array_equal(a, b), \
        "batch-axis sharding must not change any bound bit"
    assert many.last_plan.devices == 8
    # every chunk's lane count is a device multiple; the surplus lanes are
    # replicated real instances
    assert all(c.lanes % 8 == 0 for c in
               many.plan(topos, dems).chunks)
    assert many.last_plan.lanes_padded > 0


@needs_8_devices
def test_sharded_uneven_batch_to_device_split():
    # 5 equal-size instances over 8 devices: single chunk padded 5 -> 8
    topos, dems = _instances([16] * 5)
    eng = DualEngine(iters=150, devices=8)
    plan = eng.plan(topos, dems)
    assert [c.lanes for c in plan.chunks] == [8]
    assert plan.stats.lanes_padded == 3
    got = _bounds(eng.solve_batch(topos, dems))
    ref = _bounds(DualEngine(iters=150, devices=1).solve_batch(topos, dems))
    assert np.array_equal(got, ref)


@needs_8_devices
def test_sharded_chunking_under_tiny_lane_budget():
    # budget below the device count is bumped to one lane per device;
    # a non-multiple budget floors to the device multiple
    topos, dems = _instances([16] * 20)
    eng = DualEngine(iters=120, tol=1e-3, devices=8, max_lanes=12)
    plan = eng.plan(topos, dems)
    assert all(c.lanes == 8 for c in plan.chunks)       # 12 -> floor -> 8
    assert [len(c.indices) for c in plan.chunks] == [8, 8, 4]
    got = _bounds(eng.solve_batch(topos, dems))
    ref = _bounds(DualEngine(iters=120, tol=1e-3, devices=1,
                             bucket="pow2").solve_batch(topos, dems))
    # early stopping is per-chunk: a chunk may retire at a different check
    # window than the whole-bucket batch, so compare loosely
    assert got == pytest.approx(ref, rel=5e-3)


@needs_8_devices
def test_sharded_primal_bounds_bit_identical_to_single_device():
    # the primal FW solver rides the same sharded plan machinery
    from repro.core.engine import PrimalEngine
    topos, dems = _instances([12, 14, 16, 16, 20])
    a = _bounds(PrimalEngine(iters=120, devices=1).solve_batch(topos, dems))
    b = _bounds(PrimalEngine(iters=120, devices=8).solve_batch(topos, dems))
    assert np.array_equal(a, b), \
        "batch-axis sharding must not change any primal bound bit"


@needs_8_devices
def test_sharded_empty_and_single_instance():
    assert DualEngine(devices=8).solve_batch([], []) == []
    topos, dems = _instances([16])
    got = DualEngine(iters=150, devices=8).solve_batch(topos, dems)
    ref = mcf.solve_dual(topos[0], dems[0], iters=150)
    assert got[0].throughput == pytest.approx(ref.throughput_ub, rel=1e-4)


SHARDED_SCRIPT = textwrap.dedent("""
    import json, sys
    import numpy as np
    sys.path.insert(0, sys.argv[1])
    from test_plan import _instances
    from repro.core.engine import CertifiedEngine
    topos, dems = _instances([12, 16, 16])
    out = {}
    for ndev in (1, 4):
        eng = CertifiedEngine(iters=60, devices=ndev)
        out[ndev] = [(r.meta["lb"], r.meta["ub"])
                     for r in eng.solve_batch(topos, dems)]
    print(json.dumps({"same": out[1] == out[4], "devices": len(out)}))
""")


def test_sharded_plan_on_four_host_devices():
    """The sharded path in the plain one-device run: a child process with
    four virtual CPU devices solves certified brackets (whose backward
    batches a ``lax.cond``) on four devices and on one, bit-identically."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(here, "..", "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT, here],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "same": True, "devices": 2}


def test_bucket_size_reexport_consistency():
    from repro.core import engine as engine_mod
    assert engine_mod.bucket_size is bucket_size

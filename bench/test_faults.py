"""The comparison that decides ``correct`` catches a broken timed path.

Drives ``bench/run.py``'s run on the CPU (the look for a chip skipped) at
a size a test run holds: fabrics of 24 switches, 60 descent iterations,
every bracket of the window checked.  A sound run comes out correct; each
fault planted under the timed path makes it incorrect:

* ``frozen``: the solver's descent leaves its state as it found it;
* ``half``: half of each batch is left out and its answers copied from
  the other half;
* ``altered``: one answer is altered where the engine produces it;
* ``compile``: a program compiles inside the window.

The control, the reference computed in bfloat16 and put in the system's
place (``bench/control.py``, which reads it on the chip at the cells' own
sizes), also makes the run incorrect.

The sharded cell has no exchange between chips (its lanes are
independent), so no fault leaves one out.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_faults.py
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import control  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.files import load_module  # noqa: E402

ITERS = 60
CFG = {"name": "rrg-tiny",
       "fabric": {"family": "random_regular", "switches": 24,
                  "network_ports": 4, "servers_per_switch": 2},
       "solver": {"engine": "certified", "iters": ITERS, "lr": 0.08,
                  "tol": 0.0, "check_every": 25, "precision": "float32"}}
WL = {"config": "rrg-tiny", "chips": 1, "entry": "solve_batch",
      "traffic": {"pattern": "permutation", "fabrics_per_call": 4,
                  "pool": {"key": 5, "calls": 2}},
      "engine": {"devices": 1},
      "reference": {"lanes": 8, "block": 4},
      "limits": json.loads((ROOT / "bench" / "workloads"
                            / "rrg640-perm.json").read_text())["limits"]}


def _run(seed: int = 7) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    run = bench_run.Run("tiny", WL, CFG, seed, 0.0, False, bench)
    return bench_run.execute(run)


@pytest.fixture(autouse=True)
def _cpu(monkeypatch):
    """Skip the harness's look for a chip; run on the CPU."""
    import jax
    if jax.devices()[0].platform != "cpu":
        pytest.skip("runs on the CPU")
    monkeypatch.setattr(bench_run, "_require_chips",
                        lambda chips: jax.devices()[:chips])
    jax.clear_caches()   # a planted fault must reach a fresh program


def test_sound_run_is_correct():
    line = _run()
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 4


def test_fabric_that_cannot_route_is_answered_zero(monkeypatch):
    """theta* = 0 when a demanded pair has no path: lb = 0 is the right
    answer, and such a bracket has no width to average."""
    family = load_module("fabrics", "random_regular")
    orig = family.build
    drawn = []

    def split_in_two(spec, rng):
        drawn.append(spec)
        if len(drawn) > 1:   # only the first fabric of the window
            return orig(spec, rng)
        n = spec["switches"]
        cap = np.zeros((n, n))
        half = n // 2
        cap[:half, :half] = orig({**spec, "switches": half}, rng)
        cap[half:, half:] = orig({**spec, "switches": n - half}, rng)
        return cap

    monkeypatch.setattr(family, "build", split_in_two)
    line = _run()
    assert line["correct"], line["checks"]
    assert line["metrics"]["gap_mean_pct"]["value"] < 50.0


def test_frozen_descent_is_caught(monkeypatch):
    """The descent steps once and then hands back its state unchanged,
    reporting the full budget: a valid but loose bracket."""
    from repro.core import primal
    orig = primal._solve_one

    def frozen(cap, dem, n_valid, lr_peak, tol, *, iters, **kw):
        lb, ub, util, it = orig(cap, dem, n_valid, lr_peak, tol, iters=1,
                                **kw)
        return lb, ub, util, it * 0 + iters

    monkeypatch.setattr(primal, "_solve_one", frozen)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["bad_brackets"]["value"] == 0
    assert line["checks"]["ub_rel_diff"]["value"] > \
        line["checks"]["ub_rel_diff"]["limit"]


def test_half_batch_is_caught(monkeypatch):
    from repro.core import engine
    orig = engine.CertifiedEngine.solve_batch

    def half(self, topos, dems):
        k = len(topos) // 2
        got = orig(self, topos[:k], dems[:k])
        return got + got[:len(topos) - k]

    monkeypatch.setattr(engine.CertifiedEngine, "solve_batch", half)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["ub_rel_diff"]["value"] > \
        line["checks"]["ub_rel_diff"]["limit"]


def test_altered_answer_is_caught(monkeypatch):
    from repro.core import engine
    orig = engine.CertifiedEngine._result

    def altered(self, s):
        r = orig(self, s)
        meta = {**r.meta, "ub": r.meta["ub"] * 1.001}
        return dataclasses.replace(r, meta=meta)

    monkeypatch.setattr(engine.CertifiedEngine, "_result", altered)
    line = _run()
    assert not line["correct"]


def test_compile_in_window_is_caught(monkeypatch):
    solve_batch = load_module("entries", "solve_batch")
    orig = solve_batch.call

    def recompiling(state, inputs):
        state["engine"].iters += 1   # a new static: a new program
        return orig(state, inputs)

    monkeypatch.setattr(solve_batch, "call", recompiling)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["compiles_in_window"]["value"] > 0


def test_bfloat16_control_fails():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    run = bench_run.Run("tiny", WL, CFG, 11, 0.0, False, bench)
    with control.as_control(run):
        line = bench_run.execute(run)
    assert not line["correct"]
    assert line["checks"]["bad_brackets"]["value"] == 0

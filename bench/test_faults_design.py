"""The comparison that decides ``correct`` catches a broken design search.

Drives ``bench/run.py``'s run of the ``vl2-design`` cell on the CPU (the
look for a chip skipped) at a size a test run holds: VL2Spec(6, 6) with 12
ToRs, a fleet of 6, 100 iterations, every lane of the window checked.  A
sound run comes out correct; each fault planted under the timed path makes
it incorrect:

* ``capacity``: a swap move that also adds one link, so one switch has
  more capacity than the recipe gives it (``bad_instances``);
* ``rank_ub``: every ranking upper bound lowered by 1%
  (``rank_ub_rel_diff``; ``rank_ub_under_ref_lb`` catches only a bound
  lowered by more than its bracket's width);
* ``best``: a reported best that is not the argmax of the certified lower
  bounds (``selection_mismatch``).

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_faults_design.py
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import control  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.files import load_json  # noqa: E402

CFG = load_json(ROOT / "bench" / "configs" / "vl2-da22.json")
CFG["equipment"] = {**CFG["equipment"], "d_a": 6, "d_i": 6, "n_tor": 12}
CFG["solver"] = {**CFG["solver"], "iters": 100}
WL = load_json(ROOT / "bench" / "workloads" / "vl2-design.json")
WL["search"] = {**WL["search"], "fleet": 6, "runs": 2, "elite": 2}
WL["reference"] = {"lanes": 64, "rank_lanes": 64, "block": 8}


def _run(seed: int = 7) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    run = bench_run.Run("vl2-design", WL, CFG, seed, 0.0, False, bench)
    return bench_run.execute(run)


@pytest.fixture(autouse=True)
def _cpu(monkeypatch):
    """Skip the harness's look for a chip; run on the CPU."""
    import jax
    if jax.devices()[0].platform != "cpu":
        pytest.skip("runs on the CPU")
    monkeypatch.setattr(bench_run, "_require_chips",
                        lambda chips: jax.devices()[:chips])


def test_sound_run_is_correct():
    line = _run()
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] in (4, 6)


def test_capacity_changing_swap_is_caught(monkeypatch):
    from repro.design import moves
    orig = moves.MOVES["swap"]
    n_tor = CFG["equipment"]["n_tor"]

    def swap_and_add(cand, rng, space):
        new = orig(cand, rng, space)
        cap = new.topo.cap.copy()
        cap[n_tor, n_tor + 6] += space.link_unit
        cap[n_tor + 6, n_tor] += space.link_unit
        return dataclasses.replace(
            new, topo=dataclasses.replace(new.topo, cap=cap))

    monkeypatch.setitem(moves.MOVES, "swap", swap_and_add)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["bad_instances"]["value"] > 0


def test_lowered_ranking_bound_is_caught(monkeypatch):
    from repro.core import plan
    orig = plan.SOLVERS["dual"]

    def lowered(*args):
        out = orig(*args)
        return {**out, "value": out["value"] * 0.99}

    monkeypatch.setitem(plan.SOLVERS, "dual", lowered)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["rank_ub_rel_diff"]["value"] > \
        line["checks"]["rank_ub_rel_diff"]["limit"]


def test_best_that_is_not_the_argmax_is_caught(monkeypatch):
    import repro.design
    orig = repro.design.optimize

    def worst_best(*args, **kwargs):
        result = orig(*args, **kwargs)
        worst = min(result.elites + [result.reference], key=lambda e: e.lb)
        assert worst.lb < result.best.lb
        return dataclasses.replace(result, best=worst)

    monkeypatch.setattr(repro.design, "optimize", worst_best)
    line = _run()
    assert not line["correct"]
    assert line["checks"]["selection_mismatch"]["value"] > 0


def test_bfloat16_control_fails():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    run = bench_run.Run("vl2-design", WL, CFG, 11, 0.0, False, bench)
    with control.as_control(run):
        line = bench_run.execute(run)
    assert not line["correct"]

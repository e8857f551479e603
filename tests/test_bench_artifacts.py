"""BENCH_<name>.json artifact schema pinning.

``benchmarks.run`` writes one machine-readable artifact per figure; CI
uploads them and downstream tooling tracks the perf trajectory across PRs.
These tests pin the key sets (top-level payload, the per-figure stats
block, plan-stats, per-row bracket columns) so artifact consumers do not
break silently when the benchmark harness evolves.
"""
import json

import numpy as np
import pytest

from benchmarks import (adversarial_bench, design_bench, lifecycle_bench,
                        routing_bench, scale_bench)
from benchmarks.common import (bench_extra, bracket_cols, max_bracket_gap,
                               write_bench_json)
from repro.core import graphs, traffic
from repro.core.engine import DualEngine, SweepPoint
from repro.core.plan import PlanStats

# the pinned contracts -------------------------------------------------------

PAYLOAD_KEYS = {"name", "generated_unix", "wall_s", "headline", "rows"}
EXTRA_KEYS = {"scale", "engine", "compiles", "last_plan", "max_gap"}
PLAN_STATS_KEYS = {"instances", "buckets", "chunks", "devices", "max_lanes",
                   "lanes_total", "lanes_padded", "compile_keys"}
DESIGN_ROW_KEYS = {"figure", "space", "rounds", "fleet", "elite", "runs",
                   "executes", "search_executes", "compile_keys",
                   "instances_per_round", "recipe_lb", "best_lb", "best_ub",
                   "design_gain_pct", "wall_s"}
DESIGN_EXTRA_KEYS = {"compile_keys", "last_plan", "rounds", "fleet"}
LIFECYCLE_ROW_KEYS = {"figure", "family", "kind", "fraction", "trials",
                      "lb_q10", "lb_med", "lb_q90", "ub_mean", "gap_max",
                      "reachable_mean", "dead_trials"}
LIFECYCLE_EXTRA_KEYS = {"compile_keys", "executes", "refills", "last_plan",
                        "expansion"}
EXPANSION_STEP_KEYS = {"step", "nodes", "new_switches", "new_ports",
                       "spare_ports", "recabled", "lb", "ub", "lb_source",
                       "chose"}
SCALE_ROW_KEYS = {"figure", "section", "backend", "label", "n", "padded_n",
                  "ok", "wall_s", "mem_gb", "peak_rss_mb", "d_max", "rounds",
                  "lb", "ub", "compiles", "hits"}
SCALE_EXTRA_KEYS = {"mem_budget_gb", "time_budget_s", "frontier",
                    "coarsen_equal", "warm_over_cold", "last_plan"}
ADVERSARIAL_ROW_KEYS = {"figure", "family", "n", "rounds", "candidates",
                        "executes", "search_executes", "compile_keys",
                        "baseline_lb", "baseline_ub", "adversarial_lb",
                        "adversarial_ub", "uniform_gap_pct", "wall_s"}
ADVERSARIAL_EXTRA_KEYS = {"compile_keys", "last_plan", "rounds", "candidates"}
ROUTING_ROW_KEYS = {"figure", "family", "n", "pattern", "runs", "k",
                    "ideal_lb", "ideal_ub", "ecmp_lb", "ksp_lb",
                    "ecmp_gap_pct", "ksp_gap_pct", "executes",
                    "compile_keys", "wall_s"}
ROUTING_EXTRA_KEYS = {"compile_keys", "last_plan", "k", "iters",
                      "round2_new_compiles"}


def _write(tmp_path, rows, extra=None):
    path = write_bench_json("schema_probe", rows, headline="h", wall_s=1.2,
                            extra=extra, out_dir=str(tmp_path))
    with open(path) as f:
        return path, json.load(f)


def test_payload_top_level_keys(tmp_path):
    rows = [{"figure": "fig5", "bias": 0.5, "throughput": 1.0}]
    path, payload = _write(tmp_path, rows)
    assert path.endswith("BENCH_schema_probe.json")
    assert set(payload) == PAYLOAD_KEYS
    assert payload["rows"] == rows
    assert payload["headline"] == "h" and payload["wall_s"] == 1.2


def test_payload_with_figure_stats_block(tmp_path):
    extra = bench_extra(scale="small", engine="certified",
                        compiles={"dual.solve_batch": 1}, last_plan=None)
    extra["max_gap"] = 0.03
    rows = [{"figure": "fig5", "bias": 0.5, "throughput": 1.0, "gap": 0.03}]
    _, payload = _write(tmp_path, rows, extra)
    assert set(payload) == PAYLOAD_KEYS | EXTRA_KEYS
    assert payload["max_gap"] == 0.03
    assert payload["engine"] == "certified"


def test_bench_extra_key_contract():
    extra = bench_extra(scale="small", engine="dual", compiles={},
                        last_plan=None)
    assert set(extra) == EXTRA_KEYS


def test_plan_stats_keys_and_json_round_trip(tmp_path):
    topo = graphs.random_regular_graph(8, 3, 0, servers=2)
    dem = traffic.make("permutation", topo.servers, 1)
    eng = DualEngine(iters=5, devices=1)
    eng.solve_batch([topo], [dem])
    stats = eng.last_plan.as_dict()
    assert isinstance(eng.last_plan, PlanStats)
    assert set(stats) == PLAN_STATS_KEYS
    # the dict must survive the artifact's JSON encoding (compile_keys is
    # a tuple of tuples; json maps it to nested lists)
    _, payload = _write(tmp_path, [{"figure": "probe", "x": 1}],
                        bench_extra(scale="small", engine="dual",
                                    compiles={}, last_plan=stats))
    assert set(payload["last_plan"]) == PLAN_STATS_KEYS
    assert payload["last_plan"]["instances"] == 1
    assert payload["last_plan"]["compile_keys"] == [[8, 1]]


def test_max_bracket_gap_and_bracket_cols():
    pts = [SweepPoint(0.5, 1.0, 0.0, (1.0,), lb_mean=0.97, gap_max=0.03),
           SweepPoint(1.0, 1.1, 0.0, (1.1,), lb_mean=1.05, gap_max=0.045)]
    rows = [{"figure": "f", "x": p.x, "throughput": p.mean,
             **bracket_cols(p)} for p in pts]
    assert all(r["gap"] == p.gap_max for r, p in zip(rows, pts))
    assert max_bracket_gap(rows) == pytest.approx(0.045)
    # engines without brackets add no column and report no gap
    bare = SweepPoint(0.5, 1.0, 0.0, (1.0,))
    assert bracket_cols(bare) == {}
    assert max_bracket_gap([{"figure": "f", "x": 1.0}]) is None


def test_design_artifact_schema(tmp_path):
    """BENCH_design.json: the designer bench's row/extra key sets are
    pinned here AND asserted at generation time inside ``bench`` itself
    (CI's ``design_bench --smoke`` runs the real thing; this test keeps
    the contract visible and the payload JSON-able without paying for a
    search)."""
    assert design_bench.DESIGN_ROW_KEYS == DESIGN_ROW_KEYS
    assert design_bench.DESIGN_EXTRA_KEYS == DESIGN_EXTRA_KEYS
    row = dict.fromkeys(DESIGN_ROW_KEYS, 1)
    row.update(figure="design", space="vl2")
    extra = {"compile_keys": [[10, 8], [10, 6]],
             "last_plan": None, "rounds": 1, "fleet": 4}
    path = write_bench_json("design", [row], headline="h", wall_s=0.1,
                            extra=extra, out_dir=str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert path.endswith("BENCH_design.json")
    assert set(payload) == PAYLOAD_KEYS | DESIGN_EXTRA_KEYS
    assert set(payload["rows"][0]) == DESIGN_ROW_KEYS
    assert payload["compile_keys"] == [[10, 8], [10, 6]]


def test_adversarial_artifact_schema(tmp_path):
    """BENCH_adversarial.json: the worst-TM bench's row/extra key sets are
    pinned here AND asserted at generation time inside ``bench`` (CI's
    ``adversarial_bench --smoke`` runs the real search; this test keeps
    the contract visible and the payload JSON-able without paying for
    one)."""
    assert adversarial_bench.ADVERSARIAL_ROW_KEYS == \
        frozenset(ADVERSARIAL_ROW_KEYS)
    assert adversarial_bench.ADVERSARIAL_EXTRA_KEYS == \
        frozenset(ADVERSARIAL_EXTRA_KEYS)
    row = dict.fromkeys(ADVERSARIAL_ROW_KEYS, 1)
    row.update(figure="adversarial", family="two_cluster",
               uniform_gap_pct=18.4)
    extra = {"compile_keys": [[16, 4]], "last_plan": None,
             "rounds": 2, "candidates": 4}
    path = write_bench_json("adversarial", [row], headline="h", wall_s=0.1,
                            extra=extra, out_dir=str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert path.endswith("BENCH_adversarial.json")
    assert set(payload) == PAYLOAD_KEYS | ADVERSARIAL_EXTRA_KEYS
    assert set(payload["rows"][0]) == ADVERSARIAL_ROW_KEYS
    assert payload["compile_keys"] == [[16, 4]]


def test_routing_artifact_schema(tmp_path):
    """BENCH_routing.json: the routing-gap bench's row/extra key sets are
    pinned here AND asserted at generation time inside ``bench`` (CI's
    ``routing_bench --smoke`` runs the real trio; this test keeps the
    contract visible and the payload JSON-able without paying for it)."""
    assert routing_bench.ROUTING_ROW_KEYS == frozenset(ROUTING_ROW_KEYS)
    assert routing_bench.ROUTING_EXTRA_KEYS == frozenset(ROUTING_EXTRA_KEYS)
    row = dict.fromkeys(ROUTING_ROW_KEYS, 1)
    row.update(figure="routing", family="rrg", pattern="permutation",
               ecmp_gap_pct=34.7, ksp_gap_pct=5.1)
    extra = {"compile_keys": [[16, 6]], "last_plan": None, "k": 8,
             "iters": 400, "round2_new_compiles": {"routing.ksp_batch": 0}}
    path = write_bench_json("routing", [row], headline="h", wall_s=0.1,
                            extra=extra, out_dir=str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert path.endswith("BENCH_routing.json")
    assert set(payload) == PAYLOAD_KEYS | ROUTING_EXTRA_KEYS
    assert set(payload["rows"][0]) == ROUTING_ROW_KEYS
    assert payload["round2_new_compiles"] == {"routing.ksp_batch": 0}


def test_lifecycle_artifact_schema(tmp_path):
    """BENCH_lifecycle.json: row keys (certified degradation-curve points
    with ``reachable_mean``), the extra block (plan accounting + the
    expansion trajectory), and the per-step keys inside it — pinned here
    AND asserted at generation inside ``bench`` (CI's ``lifecycle_bench
    --smoke`` runs the real thing)."""
    assert lifecycle_bench.LIFECYCLE_ROW_KEYS == LIFECYCLE_ROW_KEYS
    assert lifecycle_bench.LIFECYCLE_EXTRA_KEYS == LIFECYCLE_EXTRA_KEYS
    assert lifecycle_bench.EXPANSION_STEP_KEYS == EXPANSION_STEP_KEYS
    row = dict.fromkeys(LIFECYCLE_ROW_KEYS, 1.0)
    row.update(figure="lifecycle", family="rrg", kind="links")
    step = dict.fromkeys(EXPANSION_STEP_KEYS, 0)
    step.update(lb_source="measured", chose="attached")
    extra = {"compile_keys": [[24, 24], [10, 12]], "executes": 3,
             "refills": 2, "last_plan": None,
             "expansion": {"steps": [step], "max_recabled_links": 2,
                           "growth_gain_pct": 1.5, "executes": 8,
                           "compile_keys": [[8, 2]]}}
    path = write_bench_json("lifecycle", [row], headline="h", wall_s=0.1,
                            extra=extra, out_dir=str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert path.endswith("BENCH_lifecycle.json")
    assert set(payload) == PAYLOAD_KEYS | LIFECYCLE_EXTRA_KEYS
    assert set(payload["rows"][0]) == LIFECYCLE_ROW_KEYS
    assert all(set(s) == EXPANSION_STEP_KEYS
               for s in payload["expansion"]["steps"])


def test_scale_artifact_schema(tmp_path):
    """BENCH_scale.json: uniform row schema across the frontier / coarsen
    / aot sections plus the scale extra block — pinned here AND asserted
    at generation inside ``bench`` (CI's ``scale_bench --smoke`` runs the
    real thing)."""
    assert scale_bench.SCALE_ROW_KEYS == SCALE_ROW_KEYS
    assert scale_bench.SCALE_EXTRA_KEYS == SCALE_EXTRA_KEYS
    row = dict.fromkeys(scale_bench._ROW_ORDER)
    row.update(figure="scale", section="host-frontier", backend="ell-bf",
               label="host-apsp-16384", n=16384, ok=True, wall_s=60.0,
               mem_gb=1.34, peak_rss_mb=1340.0, d_max=16, rounds=4)
    extra = {"mem_budget_gb": 1.5, "time_budget_s": 150.0,
             "frontier": {"squaring": 512, "blocked-fw": 4096,
                          "ell-bf": 16384},
             "coarsen_equal": True, "warm_over_cold": 0.1,
             "last_plan": None}
    path = write_bench_json("scale", [row], headline="h", wall_s=0.1,
                            extra=extra, out_dir=str(tmp_path))
    with open(path) as f:
        payload = json.load(f)
    assert path.endswith("BENCH_scale.json")
    assert set(payload) == PAYLOAD_KEYS | SCALE_EXTRA_KEYS
    assert set(payload["rows"][0]) == SCALE_ROW_KEYS
    assert payload["frontier"]["blocked-fw"] == 4096
    assert payload["frontier"]["ell-bf"] == 16384


def test_rows_with_numpy_scalars_stay_json_able(tmp_path):
    rows = [{"figure": "probe", "n": np.int64(16),
             "throughput": np.float32(0.5), "gap": np.float64(0.01)}]
    _, payload = _write(tmp_path, rows)
    assert payload["rows"][0]["n"] == 16
    assert payload["rows"][0]["throughput"] == pytest.approx(0.5)

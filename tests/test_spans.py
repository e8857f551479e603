"""Host spans and device op scopes (``repro.core.spans``), and the
benchmark's per-layer readers of them (``bench/scopes.py``,
``bench/metrics/``)."""
import collections
import contextlib

import jax
import numpy as np
import pytest

from bench import trace
from bench.files import load_module
from bench.run import Run
from repro.core import aotcache, get_engine, graphs, spans, traffic
from repro.core import heterogeneous as het


def _by_name(recs):
    return {r.name: r for r in recs}


def test_span_nesting_parents_roots_and_counts():
    spans.clear()
    with spans.span("outer", instances=3) as outer:
        with spans.span("inner.a") as a:
            a.set(iterations=7, stalled=True)
        with spans.span("inner.b"):
            pass
    with spans.span("other"):
        pass
    recs = spans.records()
    assert [r.name for r in recs] == ["inner.a", "inner.b", "outer", "other"]
    got = _by_name(recs)
    assert got["outer"].parent is None and got["outer"].root == outer.id
    for name in ("inner.a", "inner.b"):
        assert got[name].parent == outer.id
        assert got[name].root == outer.id
        assert outer.start <= got[name].start <= got[name].end <= outer.end
    assert got["other"].root == got["other"].id != outer.id
    assert got["outer"].counts == {"instances": 3}
    assert got["inner.a"].counts == {"iterations": 7, "stalled": 1}
    assert got["inner.b"].counts == {}


def test_span_closes_on_error_and_clear_empties():
    spans.clear()
    with pytest.raises(ValueError):
        with spans.span("fails"):
            raise ValueError("boom")
    (rec,) = spans.records()
    assert rec.name == "fails" and rec.end >= rec.start
    spans.records().clear()            # a copy: the buffer keeps its span
    assert len(spans.records()) == 1
    spans.clear()
    assert spans.records() == []


def test_span_buffer_is_bounded(monkeypatch):
    assert spans._records.maxlen == spans.MAX_RECORDS
    monkeypatch.setattr(spans, "_records", collections.deque(maxlen=4))
    for k in range(6):
        with spans.span(f"s{k}"):
            pass
    assert [r.name for r in spans.records()] == ["s2", "s3", "s4", "s5"]


def test_span_as_decorator_times_each_call():
    @spans.span("deco", rounds=0)
    def work(k):
        spans.current().set(rounds=k)
        return k

    spans.clear()
    assert spans.current() is None
    with spans.span("outer") as outer:
        assert spans.current() is outer
        assert [work(1), work(2)] == [1, 2]
    recs = [r for r in spans.records() if r.name == "deco"]
    assert [r.counts for r in recs] == [{"rounds": 1}, {"rounds": 2}]
    assert recs[0].id != recs[1].id
    assert all(r.parent == outer.id for r in recs)
    assert spans.current() is None


def _instance(n, seed):
    topo = graphs.random_regular_graph(n, 4, seed=seed, servers=2)
    return topo, traffic.make("permutation", topo.servers, seed=seed + 1)


def test_solve_batch_spans_follow_the_plan():
    (t1, d1), (t2, d2) = _instance(10, 1), _instance(20, 3)
    eng = get_engine("certified", iters=5)
    spans.clear()
    eng.solve_batch([t1, t2, t1], [d1, d2, d1])
    recs = spans.records()
    (root,) = [r for r in recs if r.name == "engine.solve_batch"]
    assert root.parent is None and root.counts == {}
    inner = sorted((r for r in recs if r.root == root.id and r is not root),
                   key=lambda r: r.start)
    stats = eng.last_plan
    assert stats.chunks == 2
    assert [r.name for r in inner] == (
        ["engine.prepare", "plan.build"]
        + ["plan.pack", "plan.dispatch"] * stats.chunks
        + ["plan.sync", "plan.unpack"])
    assert all(r.parent == root.id for r in inner)
    assert all(root.start <= r.start <= r.end <= root.end for r in inner)
    # the plan's shape stays in PlanStats: the spans carry no counts
    assert all(r.counts == {} for r in inner)


def test_run_sweeps_spans_cover_build_and_solve():
    spec = het.TwoClassSpec(n_large=4, k_large=8, n_small=6, k_small=4,
                            num_servers=14)
    eng = get_engine("certified", iters=5)
    spans.clear()
    het.combined_sweep(spec, [(2, 1)], [0.5, 1.0], runs=2, seed0=3,
                       engine=eng)
    recs = spans.records()
    (root,) = [r for r in recs if r.name == "engine.run_sweeps"]
    assert root.parent is None and root.counts == {}
    tree = [r for r in recs if r.root == root.id]
    assert len(tree) == len(recs)
    top = sorted((r for r in tree if r.parent == root.id),
                 key=lambda r: r.start)
    assert [r.name for r in top] == ["sweep.build", "engine.solve_batch"]
    build = top[0]
    assert build.counts == {}
    repairs = [r for r in tree if r.name == "graphs.repair"]
    assert len(repairs) >= 4
    for r in repairs:
        assert r.parent == build.id
        assert r.counts["iterations"] >= 0
        assert r.counts["stalled"] in (0, 1)
    assert {r.name for r in tree if r.parent == top[1].id} >= {
        "plan.build", "plan.pack", "plan.dispatch", "plan.sync",
        "plan.unpack"}


@pytest.mark.parametrize("backend", ["squaring", "ell-bf"])
def test_op_scopes_place_each_scope(monkeypatch, backend):
    monkeypatch.setattr(spans, "_programs", {})
    topo, dem = _instance(12, 5)
    eng = get_engine("certified", iters=5, backend=backend)
    eng.solve_batch([topo, topo], [dem, dem])
    eng.solve_batch([topo, topo], [dem, dem])      # same program: one note
    assert len(spans._programs) == 1
    counts = collections.Counter(spans.op_scopes().values())
    for scope in spans.SCOPES:
        assert counts[scope] > 0, (scope, counts)


def test_op_scopes_read_the_executable_jit_compiled(monkeypatch):
    """The map of a jit-dispatched program comes from jit's own compile:
    reading it compiles nothing more."""
    monkeypatch.setattr(spans, "_programs", {})
    compiles = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        topo, dem = _instance(14, 9)
        get_engine("certified", iters=5).solve_batch([topo], [dem])
        before = len(compiles)
        assert before > 0
        scopes = spans.op_scopes()
        assert len(compiles) == before
    finally:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(listen)
    assert set(scopes.values()) >= set(spans.SCOPES)


def test_aot_entries_carry_their_scope_map(monkeypatch, tmp_path):
    """Through the AOT cache the map is computed once, at compile, kept
    in the entry, and served with a warm hit."""
    topo, dem = _instance(12, 11)
    monkeypatch.setattr(spans, "_programs", {})
    cold = get_engine("certified", iters=5, aot_cache=str(tmp_path))
    cold.solve_batch([topo], [dem])
    (key,) = spans._programs
    assert key in cold._aot.entries()
    assert spans._programs[key] == cold._aot.last["scopes"]
    first = spans.op_scopes()
    monkeypatch.setattr(spans, "_programs", {})
    hits = aotcache.stats()["hits"]
    warm = get_engine("certified", iters=5, aot_cache=str(tmp_path))
    warm.solve_batch([topo], [dem])
    assert aotcache.stats()["hits"] == hits + 1
    assert spans.op_scopes() == first
    assert set(first.values()) >= set(spans.SCOPES)


def test_op_scopes_mark_a_name_two_programs_disagree_on(monkeypatch):
    monkeypatch.setattr(spans, "_programs", {
        1: {"fusion.1": "apsp_bwd", "fusion.2": "apsp_fwd", "copy.3": None},
        2: {"fusion.1": "descent_update", "fusion.2": "apsp_fwd"}})
    assert spans.op_scopes() == {"fusion.1": None, "fusion.2": "apsp_fwd",
                                 "copy.3": None}


HLO = """\
HloModule jit_step

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0.1, %param_0.1), \
metadata={op_name="jit(f)/while/body/descent_update/add"}
}

%body.2 (arg.2: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg.2 = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%arg.2), index=1
  %copy.5 = f32[4]{0} copy(%gte.1)
  %fusion.7 = f32[4]{0} fusion(%copy.5), kind=kLoop, \
calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/\
descent_update/transpose(jvp(apsp_bwd))/mul"}
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%gte.1, %fusion.7)
}

%cond.3 (arg.3: (s32[], f32[4])) -> pred[] {
  %arg.3 = (s32[], f32[4]{0}) parameter(0)
  ROOT %constant.9 = pred[] constant(false)
}

ENTRY %main.4 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %copy.8 = f32[4]{0} copy(%p.1)
  %sort.3 = f32[4]{0} sort(%copy.8), \
metadata={op_name="jit(f)/vmap(apsp_fwd)/sort"}
  %while.6 = (s32[], f32[4]{0}) while(%sort.3), condition=%cond.3, \
body=%body.2, metadata={op_name="jit(f)/while/body/descent_update/while"}
  ROOT %gte.9 = f32[4]{0} get-tuple-element(%while.6), index=1
}
"""


def test_scopes_of_hlo_reads_metadata_and_inherits_from_callers():
    got = spans.scopes_of_hlo(HLO)
    assert got["sort.3"] == "apsp_fwd"
    assert got["fusion.7"] == "apsp_bwd"          # the innermost scope wins
    assert got["while.6"] == "descent_update"
    # no metadata: the scope of the instruction calling the computation
    assert got["copy.5"] == "descent_update"
    assert got["constant.9"] == "descent_update"
    assert got["add.1"] == "descent_update"
    # the entry computation's own plumbing is in no scope
    assert got["copy.8"] is None and got["gte.9"] is None


def test_named_scopes_leave_brackets_bit_identical(monkeypatch):
    topo, dem = _instance(12, 7)

    def solve():
        jax.clear_caches()
        res = get_engine("certified", iters=30).solve_batch(
            [topo, topo], [dem, dem])
        return np.array([(r.meta["lb"], r.meta["ub"]) for r in res])

    scoped = solve()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = solve()
    monkeypatch.undo()
    jax.clear_caches()
    assert np.array_equal(scoped.view(np.uint64), plain.view(np.uint64))


# -- the benchmark's readers, on a hand-made run ---------------------------

def _span(name, start, end):
    return spans.Span(name, start, end, 0, None, 0, {})


HOST_SPANS = [
    # set-up: two first dispatches (traced and compiled) before the window
    _span("plan.dispatch", 5.0, 7.5), _span("plan.dispatch", 8.0, 8.25),
    # call 0, 10 s .. 12 s: a solve_batch
    _span("plan.build", 10.0, 10.1), _span("plan.pack", 10.1, 10.2),
    _span("plan.dispatch", 10.2, 10.5), _span("plan.sync", 10.5, 11.9),
    _span("plan.unpack", 11.9, 12.0),
    # call 1, 20 s .. 24 s: a sweep whose build holds two repairs
    _span("graphs.repair", 20.5, 21.0), _span("graphs.repair", 21.0, 21.5),
    _span("sweep.build", 20.0, 22.0),
    _span("plan.build", 22.0, 22.2), _span("plan.dispatch", 22.2, 22.4),
]


def _run(workload="fig6-sweep"):
    run = Run(workload, {"chips": 1}, {}, 1, 48.0, True, {})
    run.calls = [{"index": 0, "span": (10.0, 12.0), "lanes": []},
                 {"index": 1, "span": (20.0, 24.0), "lanes": []}]
    return run


@pytest.mark.parametrize("metric,want", [
    ("plan_host_pct", 100.0 * 1.0 / 6.0),     # 0.6 s + 0.4 s of 6 s
    ("instance_build_pct", 100.0 * 2.0 / 6.0),
    ("repair_pct", 100.0 * 1.0 / 6.0),
    ("setup_compile_s", 2.75),
])
def test_host_readers_by_hand(monkeypatch, metric, want):
    monkeypatch.setattr(spans, "_records", collections.deque(HOST_SPANS))
    got = load_module("metrics", metric).read(_run())
    assert got == pytest.approx(want, rel=1e-12)


def _trace(while_child_end):
    """One chip: a backward fusion, a loop whose body runs a descent
    fusion, and the ELL round kernel; 1000 ns traced, calls in 0..600."""
    ops = [
        trace.Op("%fusion.1 = f32[4]{0} fusion(%p), kind=kLoop", 0, 100),
        trace.Op("%while.2 = (s32[]) while(%t), body=%b", 100, 400),
        trace.Op("%fusion.3 = f32[4]{0} fusion(%q), kind=kLoop", 110,
                 while_child_end),
        trace.Op('%closed_call.4 = f32[8]{0} custom-call(s32[2,1,8]{2,1,0} '
                 '%i), custom_call_target="tpu_custom_call"', 400, 500),
    ]
    return trace.Trace({0: ops}, {})


SCOPE_MAP = {"fusion.1": "apsp_bwd", "while.2": None,
             "fusion.3": "descent_update", "closed_call.4": "apsp_fwd"}


def _traced_run(while_child_end, scope_map):
    run = _run("rrg640-perm")
    run.trace = _trace(while_child_end)
    run.traced_ns = (0.0, 1000.0)
    run.traced_calls = [(0.0, 600.0)]
    return run, scope_map


@pytest.mark.parametrize("metric,want", [
    # busy 500 ns: 100 backward, 280 descent, 20 loop control, 100 forward
    ("apsp_bwd_busy_pct", 20.0),
    ("apsp_fwd_busy_pct", 20.0),
    ("descent_update_busy_pct", 56.0),
])
def test_device_readers_by_hand(monkeypatch, metric, want):
    run, scope_map = _traced_run(390, SCOPE_MAP)
    monkeypatch.setattr(spans, "op_scopes", lambda: scope_map)
    assert load_module("metrics", metric).read(run) == pytest.approx(want)


@pytest.mark.parametrize("case", ["unscoped", "unresolved", "no_program"])
@pytest.mark.parametrize("metric", ["apsp_bwd_busy_pct", "apsp_fwd_busy_pct",
                                    "descent_update_busy_pct"])
def test_device_readers_refuse_what_they_cannot_place(monkeypatch, metric,
                                                      case):
    from bench import scopes
    # unscoped: the loop keeps 80 of 500 ns (16%) to itself
    run, scope_map = _traced_run(330 if case == "unscoped" else 390,
                                 dict(SCOPE_MAP))
    if case == "unresolved":
        del scope_map["fusion.3"]
    monkeypatch.setattr(spans, "op_scopes", lambda: scope_map)
    if case == "no_program":
        monkeypatch.setattr(scopes, "_spans_module", lambda: None)
    assert load_module("metrics", metric).read(run) is None


def _counted(name, start, end, **counts):
    return spans.Span(name, start, end, 0, None, 0, counts)


DESIGN_SPANS = [
    # a search in call 0 (10 s .. 12 s): a ranking execute, a round's
    # moves, a refilled ranking execute, then the certification
    _counted("design.rank", 10.0, 10.6, lanes=4, refilled=0,
             lane_iters_used=300, lane_iters_run=400),
    _counted("design.propose", 10.6, 10.7, proposals=2, restarts=0),
    _counted("design.rank", 10.7, 11.2, lanes=4, refilled=1,
             lane_iters_used=200, lane_iters_run=200),
    _counted("design.certify", 11.2, 11.9, lanes=6, lane_iters_used=500,
             lane_iters_run=600),
    # set-up's warm-up and a search outside the calls: not counted
    _counted("design.rank", 5.0, 6.0, lanes=4, lane_iters_used=100,
             lane_iters_run=400),
    _counted("design.propose", 12.5, 13.0, proposals=2, restarts=0),
]


@pytest.mark.parametrize("metric,want", [
    ("design_propose_pct", 100.0 * 0.1 / 6.0),
    ("design_certify_pct", 100.0 * 0.7 / 6.0),
    ("rank_lane_occupancy_pct", 100.0 * 500 / 600),
])
def test_design_readers_by_hand(monkeypatch, metric, want):
    monkeypatch.setattr(spans, "_records", collections.deque(DESIGN_SPANS))
    got = load_module("metrics", metric).read(_run("vl2-design"))
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", ["plan_host_pct", "instance_build_pct",
                                    "repair_pct", "setup_compile_s",
                                    "design_propose_pct",
                                    "design_certify_pct",
                                    "rank_lane_occupancy_pct"])
def test_host_readers_report_nothing_without_program_spans(monkeypatch,
                                                           metric):
    from bench import scopes
    monkeypatch.setattr(scopes, "_spans_module", lambda: None)
    assert load_module("metrics", metric).read(_run()) is None
    monkeypatch.undo()
    monkeypatch.setattr(spans, "_records", collections.deque())
    assert load_module("metrics", metric).read(_run()) is None


@pytest.mark.parametrize("oldest_end,metric,reports", [
    # the oldest kept span closed before the window: nothing in it dropped
    (9.0, "plan_host_pct", True),
    (9.0, "repair_pct", True),
    # it closed inside the window: spans of the calls may be gone
    (10.05, "plan_host_pct", False),
    (10.05, "instance_build_pct", False),
    # set-up's first dispatches may be gone whenever the buffer is full
    (1.0, "setup_compile_s", False),
])
def test_host_readers_refuse_a_buffer_that_dropped_spans(
        monkeypatch, oldest_end, metric, reports):
    recs = [_span("engine.prepare", 0.5, oldest_end)] + HOST_SPANS
    monkeypatch.setattr(spans, "_records", collections.deque(recs))
    monkeypatch.setattr(spans, "MAX_RECORDS", len(recs))
    got = load_module("metrics", metric).read(_run())
    assert (got is not None) == reports
    monkeypatch.setattr(spans, "MAX_RECORDS", len(recs) + 1)
    assert load_module("metrics", metric).read(_run()) is not None

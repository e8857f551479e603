#!/usr/bin/env python3
"""Check the scope readers (``bench/scopes.py`` and the three
``*_busy_pct`` metrics) against a trace recorded on a TPU, kept in
``bench/fixtures/``.

    python bench/selfcheck_scopes.py            # check (any machine, no chip)
    python bench/selfcheck_scopes.py --record   # record the fixture (on a TPU)

The fixture is 30 ms of one ``rrg640-perm`` call, taken several seconds
in (a whole descent step or more: the APSP forward, its backward and the
update), and beside it the program's op -> scope map for every op of the
trace (``repro.core.spans.op_scopes()`` at record time) and the readings
then.  The check recomputes each scope's exclusive device time with a
plain sweep written here (each stretch between two op endpoints goes to
the innermost op running: the one that started last), and compares the
shares with the readers' and with the recorded ones.  Exit status 0 when
all agree.

Recording also measures what the fixture's JSON keeps under
``measured``: a host span's cost with no profiler session and under an
active one, and the device's longest idle gap in the first 0.3 s of a
call, with the time each of the program's spans (``repro.*`` annotations,
on the trace's clock) was open during it.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import scopes, trace  # noqa: E402
from bench.files import load_json, load_module  # noqa: E402

NAME = "rrg640-perm"
FIXTURE = ROOT / "bench" / "fixtures" / f"{NAME}-scopes.xplane.pb"
EXPECT = ROOT / "bench" / "fixtures" / f"{NAME}-scopes.json"
METRICS = {"apsp_fwd": "apsp_fwd_busy_pct", "apsp_bwd": "apsp_bwd_busy_pct",
           "descent_update": "descent_update_busy_pct"}
FIXTURE_S = 0.030      # traced length of the fixture
START_S = 0.3          # traced length of a call's start


def plain_scope_ns(ops, window, scope_map) -> dict:
    """Exclusive time per scope by an endpoint sweep: each stretch between
    consecutive endpoints (inside ``window``) goes to the op that started
    last among those running."""
    (lo, hi), = window
    points = sorted({t for o in ops for t in (o.start, o.end)
                     if lo <= t <= hi} | {lo, hi})
    by_start = sorted(ops, key=lambda o: (o.start, -o.end))
    out: dict = {}
    active: list = []
    k = 0
    for a, b in zip(points, points[1:]):
        while k < len(by_start) and by_start[k].start <= a:
            active.append(by_start[k])
            k += 1
        active = [o for o in active if o.end > a]
        running = [o for o in active if o.start <= a and o.end >= b]
        if not running:
            continue
        inner = max(running, key=lambda o: (o.start, -o.end))
        scope = scope_map[inner.short.split("[", 1)[0]]
        out[scope] = out.get(scope, 0.0) + (b - a)
    return out


def _run_for(tr: trace.Trace, window):
    from bench.run import Run
    wl = load_json(ROOT / "bench" / "workloads" / f"{NAME}.json")
    cfg = load_json(ROOT / "bench" / "configs" / f"{wl['config']}.json")
    run = Run(NAME, wl, cfg, 1, 0.0, True, {})
    run.trace = tr
    run.traced_ns = tuple(window[0])
    run.traced_calls = list(window)
    return run


def reader_shares(tr: trace.Trace, window, scope_map) -> dict:
    """The three metrics as the benchmark reads them, with the program's
    map replaced by ``scope_map``."""
    scopes.op_scopes = lambda: scope_map
    run = _run_for(tr, window)
    return {scope: load_module("metrics", m).read(run)
            for scope, m in METRICS.items()}


def shares(scope_ns: dict) -> dict:
    busy = sum(scope_ns.values())
    return {scope: 100.0 * scope_ns.get(scope, 0.0) / busy
            for scope in METRICS}


def _profile(directory: Path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    shutil.rmtree(directory, ignore_errors=True)
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    mark = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:clock-mark"):
        pass
    return mark


def _xplane(directory: Path) -> str:
    return glob.glob(str(directory / "**" / "*.xplane.pb"), recursive=True)[0]


def span_cost_us(n: int) -> float:
    """Mean cost of one empty host span, in microseconds."""
    from repro.core import spans
    t0 = time.perf_counter()
    for _ in range(n):
        with spans.span("cost"):
            pass
    cost = (time.perf_counter() - t0) / n * 1e6
    spans.clear()
    return cost


def program_annotations(path: str) -> list[tuple[str, float, float]]:
    """The program's spans in a trace: ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name[len("repro."):], e.start_ns, e.end_ns)
                        for e in line.events if e.name.startswith("repro.")]
    return out


def start_gap(path: str, t_call: float, t_mark: float) -> dict:
    """The device's longest idle gap from the call's start to the end of
    the trace, and how long each program span was open during it."""
    tr = trace.load(path)
    base = tr.annotations["clock-mark"][0][0]
    lo = base + (t_call - t_mark) * 1e9
    ops = [(o.start, o.end) for o in tr.devices[0]]
    hi = max(b for _, b in ops)
    gaps = trace.gaps(ops, [(lo, hi)])
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    open_s: dict[str, float] = {}
    for name, s, e in program_annotations(path):
        inside = max(0.0, min(b, e) - max(a, s))
        if inside > 0:
            open_s[name] = open_s.get(name, 0.0) + inside / 1e9
    return {"gap_s": (b - a) / 1e9, "gap_from_call_start_s": (a - lo) / 1e9,
            "spans_open_s": open_s}


def record() -> None:
    """Measure span costs; trace the first 0.3 s of an ``rrg640-perm``
    call, then 30 ms of it several seconds in; store the fixture, the
    map of its ops and the readings."""
    import threading

    import jax
    from bench import run as bench_run
    from repro.core import aotcache, spans
    aotcache.enable_jax_cache()
    bench_run.cache_every_program()
    wl = load_json(ROOT / "bench" / "workloads" / f"{NAME}.json")
    cfg = load_json(ROOT / "bench" / "configs" / f"{wl['config']}.json")
    bench_run._require_chips(wl["chips"])
    run = bench_run.Run(NAME, wl, cfg, 1, 0.0, False, {})
    entry = load_module("entries", wl["entry"])
    state = entry.setup(run)
    tmp = ROOT / ".bench_trace" / "scopes"
    measured = {"span_us_no_profiler": span_cost_us(100_000)}
    _profile(tmp / "cost")
    measured["span_us_profiler"] = span_cost_us(10_000)
    jax.profiler.stop_trace()

    inputs, _ = entry.prepare(state, 0)
    t_mark = _profile(tmp / "start")
    t_call = time.perf_counter()
    worker = threading.Thread(target=entry.call, args=(state, inputs))
    worker.start()
    time.sleep(START_S)
    jax.profiler.stop_trace()
    measured["call_start"] = start_gap(_xplane(tmp / "start"), t_call,
                                       t_mark)
    time.sleep(max(0.0, t_call + 6.0 - time.perf_counter()))
    t_mark = _profile(tmp / "fixture")
    time.sleep(FIXTURE_S)
    t_stop = time.perf_counter()
    jax.profiler.stop_trace()
    worker.join()
    FIXTURE.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(_xplane(tmp / "fixture"), FIXTURE)
    shutil.rmtree(tmp, ignore_errors=True)

    tr = trace.load(str(FIXTURE))
    base = tr.annotations["clock-mark"][0][0]
    window = [(base, base + (t_stop - t_mark) * 1e9)]
    program_map = spans.op_scopes()
    heads = {o.short.split("[", 1)[0] for o in tr.devices[0]}
    missing = sorted(heads - set(program_map))
    scope_map = {h: program_map.get(h) for h in sorted(heads)}
    scope_ns = plain_scope_ns(tr.devices[0], window,
                              {h: scope_map.get(h) for h in heads})
    EXPECT.write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind,
        "window_ns": window[0],
        "unresolved_ops": missing,
        "op_scopes": scope_map,
        "readings": {"scope_ns": {str(k): v for k, v in scope_ns.items()},
                     "shares": shares(scope_ns),
                     "readers": reader_shares(tr, window, scope_map)},
        "measured": measured}, indent=1) + "\n")
    print(f"recorded {FIXTURE} ({FIXTURE.stat().st_size} bytes); "
          f"{len(missing)} ops not in the map; {json.dumps(measured)}")


def check() -> int:
    expect = json.loads(EXPECT.read_text())
    tr = trace.load(str(FIXTURE))
    window = [tuple(expect["window_ns"])]
    scope_map = expect["op_scopes"]
    ok = not expect["unresolved_ops"]
    if not ok:
        print(f"BAD ops the program's map lacked: {expect['unresolved_ops']}")
    plain = shares(plain_scope_ns(tr.devices[0], window, scope_map))
    got = reader_shares(tr, window, scope_map)
    for scope, want in expect["readings"]["shares"].items():
        for label, other in (("plain", plain[scope]), ("recorded", want)):
            same = got[scope] is not None and math.isclose(
                got[scope], other, rel_tol=1e-9, abs_tol=1e-9)
            ok &= same
            print(f"{'ok ' if same else 'BAD'} {METRICS[scope]}: readers "
                  f"{got[scope]} {label} {other}")
    if sum(v or 0.0 for v in got.values()) < 90.0:
        print("BAD the three scopes hold under 90% of the busy time")
        ok = False
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--record", action="store_true")
    args = ap.parse_args(argv)
    if args.record:
        (ROOT / ".jax_cache").mkdir(exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        record()
        return 0
    return check()


if __name__ == "__main__":
    sys.exit(main())

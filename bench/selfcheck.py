#!/usr/bin/env python3
"""Check the trace reduction (``bench/trace.py``, ``bench/kernels.py``)
against a small trace recorded on a TPU, kept in ``bench/fixtures/``.

    python bench/selfcheck.py            # check (any machine, no chip)
    python bench/selfcheck.py --record   # record the fixture (on a TPU)

The fixture is 10 ms of one ``rrg640-perm`` call: the ELL round
kernel, the SP-DAG backward and the descent's ops on ``/device:TPU:0``,
and the benchmark's host spans.  The check recomputes device busy time,
idle gaps, the ELL kernel's count and time, and exclusive op times with
plain sweeps written here, and compares them with ``trace.py``'s readers
and with the numbers stored beside the fixture when it was recorded.
Exit status 0 when all agree.
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

from bench import kernels, trace  # noqa: E402
from bench.files import load_json, load_module  # noqa: E402

FIXTURE = ROOT / "bench" / "fixtures" / "rrg640-perm.xplane.pb"
EXPECT = ROOT / "bench" / "fixtures" / "rrg640-perm.json"


def sweep_busy(intervals) -> float:
    """Covered length by an endpoint sweep (not a merge of intervals)."""
    points = sorted([(a, 1) for a, b in intervals if b > a]
                    + [(b, -1) for a, b in intervals if b > a])
    depth, last, total = 0, None, 0.0
    for t, step in points:
        if depth > 0:
            total += t - last
        depth += step
        last = t
    return total


def readings(tr: trace.Trace, window) -> dict:
    ops = tr.devices[0]
    busy = trace.length(trace.clip([(o.start, o.end) for o in ops], window))
    ell = [o for o in ops if kernels.is_ell_round(o.name)]
    own = trace.self_times(ops, window)
    return {"ops": len(ops), "busy_ns": busy,
            "idle_ns": sum(b - a for a, b in trace.gaps(
                [(o.start, o.end) for o in ops], window)),
            "ell_rounds": len(ell),
            "ell_ns": trace.length(trace.clip(
                [(o.start, o.end) for o in ell], window)),
            "self_ns_total": sum(own.values())}


def plain(tr: trace.Trace, window) -> dict:
    (lo, hi), = window
    ops = [o for o in tr.devices[0]]
    inside = [(max(o.start, lo), min(o.end, hi)) for o in ops]
    busy = sweep_busy(inside)
    ell = [o for o in ops if "tpu_custom_call" in o.name
           and "custom-call(s32[" in o.name]
    return {"ops": len(ops), "busy_ns": busy, "idle_ns": (hi - lo) - busy,
            "ell_rounds": len(ell),
            "ell_ns": sweep_busy([(max(o.start, lo), min(o.end, hi))
                                  for o in ell]),
            # exclusive times of nested ops add up to the covered time
            "self_ns_total": busy}


def record() -> None:
    """Trace 10 ms of an ``rrg640-perm`` call, 3 s after it starts (the
    device is busy by then), and store the readings beside the trace."""
    import threading

    import jax
    from bench import run as bench_run
    from repro.core import aotcache
    aotcache.enable_jax_cache()
    bench_run.cache_every_program()
    name = "rrg640-perm"
    wl = load_json(ROOT / "bench" / "workloads" / f"{name}.json")
    cfg = load_json(ROOT / "bench" / "configs"
                    / f"{wl['config']}.json")
    bench_run._require_chips(wl["chips"])
    run = bench_run.Run(name, wl, cfg, 1, 0.0, False, {})
    entry = load_module("entries", wl["entry"])
    state = entry.setup(run)
    inputs, _ = entry.prepare(state, 0)
    worker = threading.Thread(target=entry.call, args=(state, inputs))
    worker.start()
    time.sleep(3.0)
    tmp = ROOT / ".bench_trace" / "fixture"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    t_mark = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:clock-mark"):
        pass
    time.sleep(0.01)
    t_stop = time.perf_counter()
    jax.profiler.stop_trace()
    worker.join()
    path = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)[0]
    FIXTURE.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, FIXTURE)
    shutil.rmtree(tmp, ignore_errors=True)
    tr = trace.load(str(FIXTURE))
    base = tr.annotations["clock-mark"][0][0]
    window = [(base, base + (t_stop - t_mark) * 1e9)]
    EXPECT.write_text(json.dumps({
        "device_kind": jax.devices()[0].device_kind,
        "window_ns": window[0],
        "readings": readings(tr, window)}, indent=1) + "\n")
    print(f"recorded {FIXTURE} ({FIXTURE.stat().st_size} bytes)")


def check() -> int:
    expect = json.loads(EXPECT.read_text())
    tr = trace.load(str(FIXTURE))
    window = [tuple(expect["window_ns"])]
    got, ref = readings(tr, window), plain(tr, window)
    ok = True
    for key, want in expect["readings"].items():
        for label, other in (("plain", ref[key]), ("recorded", want)):
            same = math.isclose(got[key], other, rel_tol=1e-9, abs_tol=1.0)
            ok &= same
            print(f"{'ok ' if same else 'BAD'} {key}: trace.py {got[key]} "
                  f"{label} {other}")
    if not got["ell_rounds"]:
        print("BAD the fixture holds no ELL round")
        ok = False
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--record", action="store_true")
    args = ap.parse_args(argv)
    if args.record:
        (ROOT / ".jax_cache").mkdir(exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        record()
        return 0
    return check()


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""On-chip benchmark of the certified throughput engine: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``bench/workloads/<cell>.json``.  It names its configuration
(``bench/configs/<config>.json``), the entry that drives the system
(``bench/entries/<entry>.py``), its traffic, the sample the reference
checks, and the limit of every number compared.  Metrics are read by
``bench/metrics/<metric>.py``, one file per metric named in
``BENCHMARK.json``.  Adding a cell or a metric adds files; none is edited.

A run:

1. exits with 2, printing no result, unless JAX sees a TPU with at least
   the cell's chips;
2. sets up: imports, the compile cache kept in ``.jax_cache/`` of the
   checkout, and the entry's warm-up on stand-in instances of the cell's
   shapes.  ``setup_s`` runs from the start of this script to the window;
3. drives calls back to back, starting one while fewer than ``--seconds``
   have passed since the window opened; the window closes when the last
   call returns.  Each call's instances come from ``--seed`` and the call's
   index, and a line naming them is printed before the call.  A compile
   inside the window makes the run incorrect;
4. reads the chips' peak memory, frees the system's state, and checks the
   window's brackets against the plain reference (``bench/reference.py``);
5. prints each number compared beside its limit as the last lines of
   standard error, then one JSON line as the last line of standard output:
   ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
   ``--trace 1`` a ``breakdown``, and ``checks`` last.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of part of the window (the
workload's ``trace`` entry says which part) and from the spans the
benchmark records around the system's calls.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import trace as trace_mod  # noqa: E402
from bench.files import load_json, load_module  # noqa: E402

# keyword arguments of ``get_engine`` that a configuration's ``solver``
# states (a workload's ``solver`` may override them)
SOLVER_KWARGS = ("iters", "lr", "tol", "check_every")


class NoChip(RuntimeError):
    pass


class Run:
    """What one run knows; entries fill it, metric readers read it.

    Spans are ``(name, start, end)`` in seconds of ``time.perf_counter``.
    ``calls`` holds one dict per timed call: its index, span, and
    ``lanes`` (one dict per bracket: ``cap``, ``dem``, ``lb``, ``ub``,
    ``iterations``)."""

    def __init__(self, workload: str, wl: dict, cfg: dict, seed: int,
                 seconds: float, traced: bool, bench: dict):
        self.workload, self.wl, self.cfg = workload, wl, cfg
        self.seed, self.seconds, self.traced = seed, seconds, traced
        self.bench = bench
        self.spans: list[tuple[str, float, float]] = []
        self.calls: list[dict] = []
        self.setup_s = None
        self.trace = None          # trace_mod.Trace of the traced period
        self.traced_ns = None      # (start, end) of it on the trace's clock
        self.traced_calls = []     # call spans on the trace's clock
        self.host_ns = {}          # span name -> intervals, trace's clock
        self.peaks = None
        self._tracer = None

    @contextlib.contextmanager
    def span(self, name: str):
        """Record a host span; in a traced run also annotate the trace,
        and start the profiler here if the workload traces from ``name``."""
        import jax
        if self._tracer is not None:
            self._tracer.maybe_start(name)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    @property
    def solver(self) -> dict:
        """The configuration's ``solver``, with the workload's overrides."""
        return {**self.cfg["solver"], **self.wl.get("solver", {})}

    def engine_kwargs(self) -> dict:
        kw = {k: v for k, v in self.solver.items() if k in SOLVER_KWARGS}
        return {**kw, **self.wl["engine"]}

    def call_spans(self) -> list[tuple[float, float]]:
        return [c["span"] for c in self.calls]


class Tracer:
    """Profiles part of the window: from the first time the span named
    ``start`` opens ("window" = when the window opens) until ``seconds``
    after the first time the span named ``until`` opens (by default
    ``start``)."""

    def __init__(self, directory: Path, spec: dict):
        self.dir, self.start = directory, spec["start"]
        self.until = spec.get("until", self.start)
        self.seconds = spec["seconds"]
        self.t0 = self.t1 = None
        self.mark_pc = None
        self._timer = None

    def maybe_start(self, name: str) -> None:
        if self.t0 is None and name == self.start:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.t0 = time.perf_counter()
            # one annotation ties the trace's clock to perf_counter
            self.mark_pc = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:clock-mark"):
                pass
        if self.t0 is not None and self._timer is None and name == self.until:
            self._timer = threading.Timer(self.seconds, self.stop)
            self._timer.start()

    def stop(self) -> None:
        import jax
        if self.t0 is None or self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def finish(self) -> None:
        """Stop profiling if the timer has not, and wait until the trace
        is written."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer.join()
        self.stop()


def cache_every_program() -> None:
    """Keep every compiled program in the persistent cache, with no size
    cap: capped, JAX also writes an access-time file per entry, and on
    the TPU hosts those writes failed (``FileNotFoundError``), so no
    entry was ever stored and every run compiled again."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def _jax_compile_events() -> dict[str, int]:
    """Live counts of JAX's backend compiles and persistent-cache hits
    and misses in this process."""
    import jax
    seen = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1

    def on_event(event: str, **_kw) -> None:
        for key in ("cache_hits", "cache_misses"):
            if event == f"/jax/compilation_cache/{key}":
                seen[key] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return seen


def _program_compiles() -> int:
    from repro.core import plan
    return sum(v or 0 for k, v in plan.compile_cache_sizes().items()
               if k != "aot.hits")


def _require_chips(chips: int):
    """The devices, if JAX sees a TPU with ``chips`` chips or more whose
    peaks ``peaks.json`` holds."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    if devs[0].device_kind not in load_json(BENCH / "peaks.json")["devices"]:
        raise RuntimeError(
            f"device kind {devs[0].device_kind!r} is not in peaks.json")
    return devs


def _metrics(run: Run, kind: str) -> dict:
    out = {}
    for m in run.bench[kind]:
        if "workloads" in m and run.workload not in m["workloads"]:
            continue
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _breakdown(run: Run) -> dict:
    tr = run.trace
    window = trace_mod.clip(run.traced_calls, [run.traced_ns])
    ops: dict[str, float] = {}
    busy = []
    for dev_ops in tr.devices.values():
        for name, ns in trace_mod.self_times(dev_ops, window).items():
            ops[name] = ops.get(name, 0.0) + ns / 1e9
        busy += [(o.start, o.end) for o in dev_ops]
    spans = [(n, a, b) for n, iv in run.host_ns.items() for a, b in iv]
    idle = []
    for a, b in trace_mod.gaps(busy, window):
        mid = (a + b) / 2
        inner = [s for s in spans if s[1] <= mid <= s[2]]
        name = min(inner, key=lambda s: s[2] - s[1])[0] if inner else "none"
        idle.append([name, (b - a) / 1e9])
    idle.sort(key=lambda x: -x[1])
    return {"device_ops": trace_mod.top(ops), "idle_gaps": idle[:10]}


def _read_trace(run: Run, tracer: Tracer) -> None:
    import glob
    files = glob.glob(str(tracer.dir / "**" / "*.xplane.pb"), recursive=True)
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    run.trace = trace_mod.load(files[0])
    marks = run.trace.annotations.get("clock-mark")
    if not marks:
        raise RuntimeError("the trace lacks the clock mark")
    base = marks[0][0]

    def ns(t: float) -> float:
        return base + (t - tracer.mark_pc) * 1e9

    run.traced_ns = (ns(tracer.t0), ns(tracer.t1))
    run.traced_calls = [(ns(a), ns(b)) for a, b in run.call_spans()]
    for name, a, b in run.spans:
        run.host_ns.setdefault(name, []).append((ns(a), ns(b)))
    shutil.rmtree(tracer.dir, ignore_errors=True)


def check_lines(checks: dict) -> list[str]:
    return [f"check {k}: {v['value']} limit {v['limit']}"
            for k, v in checks.items()]


def execute(run: Run) -> dict:
    """One run of the cell; returns the result line as a dict."""
    t_import = time.perf_counter()
    devs = _require_chips(run.wl["chips"])
    t_devices = time.perf_counter()
    chips = run.wl["chips"]
    from repro.core import aotcache
    aotcache.enable_jax_cache()
    cache_every_program()
    kind = devs[0].device_kind
    run.peaks = load_json(BENCH / "peaks.json")["devices"].get(kind)
    jax_compiles = _jax_compile_events()

    entry = load_module("entries", run.wl["entry"])
    t_ready = time.perf_counter()
    state = entry.setup(run)
    print(f"bench: set-up {t_import - T_START:.3f} s of imports, "
          f"{t_devices - t_import:.3f} s to the devices, "
          f"{t_ready - t_devices:.3f} s to the entry, "
          f"{time.perf_counter() - t_ready:.3f} s of warm-up; JAX {jax_compiles}",
          file=sys.stderr)

    tracer = None
    if run.traced:
        tracer = Tracer(ROOT / ".bench_trace", run.wl["trace"])
        run._tracer = tracer

    before = (_program_compiles(), jax_compiles["compiles"])
    t_open = time.perf_counter()
    run.setup_s = t_open - T_START
    if tracer is not None:
        tracer.maybe_start("window")
    k = 0
    while k == 0 or time.perf_counter() - t_open < run.seconds:
        inputs, line = entry.prepare(state, k)
        print(json.dumps({"call": k, **line}), flush=True)
        t0 = time.perf_counter()
        with run.span("call"):
            lanes, after_line = entry.call(state, inputs)
        run.calls.append({"index": k, "span": (t0, time.perf_counter()),
                          "lanes": lanes})
        if after_line:
            print(json.dumps({"call": k, **after_line}), flush=True)
        k += 1
    compiles = (_program_compiles() - before[0]
                + jax_compiles["compiles"] - before[1])
    if tracer is not None:
        tracer.finish()

    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in devs[:chips]]
    memory_peak = max((m for m in mem if m is not None), default=None)
    entry_checks = entry.check(state, run) if hasattr(entry, "check") else {}
    del state
    gc.collect()

    if tracer is not None:
        _read_trace(run, tracer)
    metrics = _metrics(run, "per_layer" if run.traced else "end_to_end")

    from bench import compare
    checks, failed = compare.check(run)
    checks.update(entry_checks)
    checks["compiles_in_window"] = {
        "value": compiles, "limit": run.wl["limits"]["compiles_in_window"]}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    line = {"correct": correct,
            "attempted": sum(len(c["lanes"]) for c in run.calls),
            "failed": failed, "metrics": metrics, "device": device}
    if run.traced:
        window = trace_mod.clip(run.traced_calls, [run.traced_ns])
        busy = trace_mod.busy_per_chip(run.trace, chips, window)
        device["busy_s"] = sum(busy) / max(len(busy), 1) / 1e9
        device["window_s"] = trace_mod.length(window) / 1e9
        line["breakdown"] = _breakdown(run)
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # compiled programs are cached inside the checkout, at a fixed path
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        wl = load_json(BENCH / "workloads" / f"{args.workload}.json")
        cfg = load_json(BENCH / "configs" / f"{wl['config']}.json")
        bench = load_json(ROOT / "BENCHMARK.json")
        run = Run(args.workload, wl, cfg, args.seed, args.seconds,
                  bool(args.trace), bench)
        line = execute(run)
    except NoChip as e:
        print(f"bench: no chip to measure on: {e}", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 - any failure is a run with no result
        traceback.print_exc()
        return 1
    for text in check_lines(line["checks"]):
        print(text, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that the limits of ``bench/compare.py`` are set from, on the
chip, at a cell's own size and load.  For each seed, two runs of the cell
through ``bench/run.py``'s own ``execute``:

* ``system``: the run as the benchmark makes it;
* ``control``: the same run with every bracket the timed calls produce
  replaced by the plain reference computed in bfloat16, the precision
  below the configuration's float32.  It has to come out not correct.

    python bench/control.py --workload rrg640-perm --seeds 11 12 13

One JSON line per run (seed, side, ``correct`` and each compared number),
then one with the largest reading of the system and the smallest of the
control for each number.  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import compare  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.files import load_json, load_module  # noqa: E402


@contextlib.contextmanager
def as_control(run):
    """Put the bfloat16 reference in the place of the system's answers:
    the entry's ``call`` still runs, then each lane's (lb, ub) is the
    control's."""
    import jax.numpy as jnp
    entry = load_module("entries", run.wl["entry"])
    orig = entry.call

    def control_call(state, inputs):
        lanes, after = orig(state, inputs)
        low = compare.reference_brackets(run, lanes, dtype=jnp.bfloat16)
        for lane, (lb, ub) in zip(lanes, low):
            lane["lb"], lane["ub"] = float(lb), float(ub)
        return lanes, after

    entry.call = control_call
    try:
        yield
    finally:
        entry.call = orig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the control on the first N seeds")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    (ROOT / ".jax_cache").mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    wl = load_json(ROOT / "bench" / "workloads" / f"{args.workload}.json")
    cfg = load_json(ROOT / "bench" / "configs" / f"{wl['config']}.json")
    bench = load_json(ROOT / "BENCHMARK.json")
    rows = []
    for i, seed in enumerate(args.seeds):
        sides = ("system", "control") if i < args.control_seeds \
            else ("system",)
        for side in sides:
            run = bench_run.Run(args.workload, wl, cfg, seed, args.seconds,
                                False, bench)
            with as_control(run) if side == "control" \
                    else contextlib.nullcontext():
                line = bench_run.execute(run)
            rows.append({"seed": seed, "side": side,
                         "correct": line["correct"],
                         "attempted": line["attempted"],
                         **{k: line["checks"][k]["value"]
                            for k in compare.NAMES}})
            print(json.dumps(rows[-1]), flush=True)
    system = [r for r in rows if r["side"] == "system"]
    control = [r for r in rows if r["side"] == "control"]
    print(json.dumps({
        "workload": args.workload, "seeds": args.seeds,
        "system_correct": all(r["correct"] for r in system),
        "control_correct_any": any(r["correct"] for r in control),
        "system_max": {k: max(r[k] for r in system) for k in compare.NAMES},
        "control_min": {k: min(r[k] for r in control)
                        for k in compare.NAMES} if control else None}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

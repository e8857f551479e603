#!/usr/bin/env python3
"""Run the certified throughput path once on a TPU and check its answers.

    python chip_smoke.py              # one chip: phases a-d
    python chip_smoke.py --chips 4    # four chips: phase c on 4 devices vs 1

Every phase goes through the entry points a user calls (``get_engine``,
``solve_batch``, ``heterogeneous.combined_sweep``):

a.  Paper-width certification: random regular graphs of 640 switches,
    10 network ports and 5 servers each (3,200 servers), permutation
    traffic, 4 seeds in one ``solve_batch`` on ``"certified"``.  ``"auto"``
    resolves to the ``ell-bf`` backend.  Each lane must satisfy
    0 < lb <= ub and lb <= the Theorem-1 bound.
b.  The same instances on ``backend="blocked-fw"`` (certified) and on
    ``"dual-pallas"`` (upper bound): no lower bound of any backend may
    exceed an upper bound of another.
c.  A Fig. 6-size design sweep: 3 server splits x 4 cross-cluster biases
    x 20 runs = 240 lanes of a 30-switch two-class fabric, one plan.
d.  Random regular graphs of 40 switches whose exact LP optimum (HiGHS)
    must lie inside the certified bracket.

Each phase prints one JSON line: engine, APSP backend, padded_n, lanes,
wall seconds (compilation included), compiles, the device's
``peak_bytes_in_use`` so far, and whether its compiled program holds a
Mosaic kernel (``tpu_custom_call``); phases a and b must.  With
``--chips 4`` only phase c runs, once sharded over four devices and once
on one, and every lane's bracket must be bit-identical.

The last line is ``{"ok": true, "device": {...}}``, printed only when every
phase passed.  The script exits non-zero on any failure, and when JAX finds
no TPU: it never falls back to the CPU.  Compiled programs are cached
under ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

RRG = dict(n=640, ports=10, servers=5)   # 3,200 servers: the paper's scale
SEEDS = (0, 1, 2, 3)
SWEEP_RUNS = 20                          # 3 splits x 4 biases x 20 = 240
LP_RRG = dict(n=40, ports=10, servers=5)
LP_SEEDS = (0, 1)
REL = 1e-4   # float32 slack when bounds from different programs meet


class SmokeError(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


class Recorder:
    """Engine wrapper that keeps the per-lane results a sweep
    aggregates away."""

    def __init__(self, eng):
        self.eng = eng
        self.topos, self.results = [], []

    def solve_batch(self, topos, dems):
        self.topos = topos
        self.results = self.eng.solve_batch(topos, dems)
        return self.results


def _compiles() -> int:
    from repro.core import plan
    return sum(v or 0 for k, v in plan.compile_cache_sizes().items()
               if k != "aot.hits")


def _peak_bytes() -> int | None:
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def run_phase(name: str, aot, fn) -> dict:
    """Run ``fn() -> facts`` and print its line.  ``mosaic_kernel`` is
    None where the program bypassed the AOT cache (a sharded plan)."""
    aot.last = {}
    c0, t0 = _compiles(), time.perf_counter()
    facts = fn()
    wall = time.perf_counter() - t0
    calls = aot.last.get("custom_calls")
    line = {"phase": name, **facts, "wall_s": wall,
            "compiles": _compiles() - c0,
            "peak_bytes_in_use": _peak_bytes(),
            "mosaic_kernel": None if calls is None
            else "tpu_custom_call" in calls}
    print(json.dumps(line), flush=True)
    return line


def _backend(eng, topos, n: int) -> str:
    """The APSP backend ``eng`` resolves to on these instances."""
    import numpy as np
    from repro.core import apsp, mcf
    from repro.core.graphs import as_cap
    caps = np.stack([as_cap(t) for t in topos])
    backend, _ = mcf.resolve_backend_density(eng.backend, caps, n=n)
    return apsp.resolve_backend(backend, n)


def rrg_instances(n: int, ports: int, servers: int, seeds):
    from repro.core import graphs, traffic
    topos = [graphs.random_regular_graph(n, ports, seed=s, servers=servers)
             for s in seeds]
    dems = [traffic.make("permutation", t.servers, seed=s + 1)
            for t, s in zip(topos, seeds)]
    return topos, dems


def solve(eng, topos, dems) -> tuple[list, dict]:
    """One ``solve_batch``; returns (results, plan facts)."""
    results = eng.solve_batch(topos, dems)
    n = results[0].meta["padded_n"]
    return results, {"engine": eng.name, "backend": _backend(eng, topos, n),
                     "padded_n": n, "lanes": len(results)}


def brackets(results) -> list[tuple[float, float]]:
    return [(r.meta["lb"], r.meta["ub"]) for r in results]


def check_brackets(label: str, got) -> None:
    for i, (lb, ub) in enumerate(got):
        check(0.0 < lb <= ub < float("inf"),
              f"{label} lane {i}: bracket [{lb}, {ub}] is not 0 < lb <= ub")


def phase_certify(aot, topos, dems, ports: int, **engine_kw):
    """Phases a and b (certified): brackets within Theorem 1."""
    from repro.core import bounds, get_engine, traffic
    eng = get_engine("certified", devices=1, aot_cache=aot, **engine_kw)
    results, facts = solve(eng, topos, dems)
    got = brackets(results)
    check_brackets(facts["backend"], got)
    for i, ((lb, _), t, d) in enumerate(zip(got, topos, dems)):
        thm1 = bounds.throughput_upper_bound(t.n, ports,
                                             traffic.num_flows(d))
        check(lb <= thm1, f"{facts['backend']} lane {i}: lb {lb} exceeds "
                          f"the Theorem-1 bound {thm1}")
    facts["brackets"] = got
    facts["gap_max"] = max(r.meta["gap"] for r in results)
    return facts


def phase_dual_pallas(aot, topos, dems, lbs):
    """Phase b: the squaring-pallas dual bound sits above every lb."""
    from repro.core import get_engine
    eng = get_engine("dual-pallas", devices=1, aot_cache=aot)
    results, facts = solve(eng, topos, dems)
    ubs = [r.throughput for r in results]
    for i, (ub, lb) in enumerate(zip(ubs, lbs)):
        check(0.0 < ub < float("inf") and lb <= ub * (1 + REL),
              f"dual-pallas lane {i}: ub {ub} below certified lb {lb}")
    facts["ub"] = ubs
    return facts


def fig6_sweep(eng, runs: int):
    """The Fig. 6 grid (``benchmarks/fig6.py``) as one plan; returns the
    recorder holding its instances and per-lane results."""
    from repro.core import heterogeneous as het
    spec = het.TwoClassSpec(10, 18, 20, 6, 90)
    rec = Recorder(eng)
    het.combined_sweep(spec, [(5, 2), (7, 1), (3, 3)], [0.3, 0.7, 1.0, 1.5],
                       runs=runs, seed0=5, engine=rec)
    return rec


def phase_sweep(aot, runs: int, devices: int):
    """Phase c: every lane of the design sweep is a bracket."""
    from repro.core import get_engine
    eng = get_engine("certified", devices=devices, aot_cache=aot)
    rec = fig6_sweep(eng, runs)
    results = rec.results
    n = results[0].meta["padded_n"]
    got = brackets(results)
    check_brackets(f"sweep devices={devices}", got)
    return {"engine": eng.name, "backend": _backend(eng, rec.topos, n),
            "padded_n": n, "lanes": len(results), "devices": devices,
            "gap_max": max(r.meta["gap"] for r in results)}, got


def phase_lp(aot, topos, dems):
    """Phase d: HiGHS's optimum lies inside the certified bracket."""
    from repro.core import get_engine
    exact = [r.throughput for r in
             get_engine("exact").solve_batch(topos, dems)]
    eng = get_engine("certified", devices=1, aot_cache=aot)
    results, facts = solve(eng, topos, dems)
    got = brackets(results)
    check_brackets("lp", got)
    for i, ((lb, ub), th) in enumerate(zip(got, exact)):
        check(lb <= th * (1 + REL) and th <= ub * (1 + REL),
              f"lp lane {i}: exact {th} outside bracket [{lb}, {ub}]")
    facts["brackets"] = got
    facts["exact"] = exact
    return facts


def one_chip(aot) -> None:
    topos, dems = rrg_instances(**RRG, seeds=SEEDS)
    a = run_phase("a", aot, lambda: phase_certify(aot, topos, dems,
                                                  RRG["ports"]))
    b = run_phase("b-fw", aot, lambda: phase_certify(
        aot, topos, dems, RRG["ports"], backend="blocked-fw"))
    for line in (a, b):
        check(line["mosaic_kernel"] is True,
              f"phase {line['phase']} ran no Mosaic kernel")
    check(a["backend"] == "ell-bf" and b["backend"] == "blocked-fw",
          f"backends resolved to {a['backend']} / {b['backend']}")
    lbs = [max(x[0], y[0]) for x, y in zip(a["brackets"], b["brackets"])]
    ubs = [min(x[1], y[1]) for x, y in zip(a["brackets"], b["brackets"])]
    for i, (lb, ub) in enumerate(zip(lbs, ubs)):
        check(lb <= ub * (1 + REL),
              f"lane {i}: ell-bf and blocked-fw brackets are disjoint")
    run_phase("b-pallas", aot, lambda: phase_dual_pallas(aot, topos, dems,
                                                         lbs))
    run_phase("c", aot, lambda: phase_sweep(aot, SWEEP_RUNS, 1)[0])
    lp_topos, lp_dems = rrg_instances(**LP_RRG, seeds=LP_SEEDS)
    run_phase("d", aot, lambda: phase_lp(aot, lp_topos, lp_dems))


def four_chips(aot) -> None:
    got = {}

    def sweep(devices):
        facts, got[devices] = phase_sweep(aot, SWEEP_RUNS, devices)
        return facts

    run_phase("c-4", aot, lambda: sweep(4))
    run_phase("c-1", aot, lambda: sweep(1))
    diff = [i for i, (x, y) in enumerate(zip(got[4], got[1])) if x != y]
    print(json.dumps({"compare": "c-4 vs c-1", "lanes": len(got[1]),
                      "lanes_differing": len(diff)}), flush=True)
    check(not diff, f"lanes {diff[:8]} differ between 4 devices and 1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded sweep and its 1-chip twin")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.core import aotcache
        devs = jax.devices()
        if devs[0].platform != "tpu":
            print(f"chip_smoke: no TPU (JAX found {devs[0].platform}); "
                  "nothing measured", file=sys.stderr)
            return 2
        check(len(devs) >= args.chips,
              f"--chips {args.chips} but JAX sees {len(devs)} device(s)")
        aotcache.enable_jax_cache()
        aot = aotcache.AotCache(aotcache.default_dir())
        (four_chips if args.chips == 4 else one_chip)(aot)
        stats = aotcache.stats()
        print(json.dumps({"aotcache": stats}), flush=True)
        check(stats["errors"] == 0, f"aotcache reported errors: {stats}")
    except Exception:  # noqa: BLE001 - any failure is a failed smoke
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Scale-frontier benchmark: APSP backend frontier, ToR-coarsened lanes,
and the persistent AOT compile cache.  Writes ``BENCH_scale.json``
(schema pinned in ``tests/test_bench_artifacts.py``).

Three sections, one uniform row schema:

* **host-frontier** — per ``ApspBackend``, the largest N whose APSP
  closure fits a fixed host-memory budget AND per-probe time budget.
  These are HOST probes: each child is pinned to the CPU
  (``JAX_PLATFORMS=cpu``) and measures its own peak RSS, so they say
  nothing about the accelerator.  Every backend
  probes the SAME degree-16 random regular graph (dense backends densify
  it; ``ell-bf`` streams the padded-ELL tables through
  ``repro.kernels.ell.ell_bf_apsp_streamed`` and never materializes a
  dense input).  Each probe is a subprocess (so ``ru_maxrss`` measures
  that probe alone and an over-budget size cannot poison the parent);
  probing stops at the first failure per backend (cost grows
  monotonically in N).  Repeated squaring materializes an O(N^3)
  broadcast, so memory caps it early; blocked Floyd-Warshall holds
  O(N^2) but pays O(N^3) work, so time caps it next; ell-bf pays
  O(N * d_max * diameter) per source block and carries the frontier past
  N=16384.  Rows record per-probe peak RSS and, for ell-bf, the
  relaxation-round count and table width.
* **coarsen** — one VL2 instance three ways: server-expanded with
  ``coarsen=False`` (models 1GbE NICs explicitly, so θ* is NIC-limited
  and lanes carry the full node count), server-expanded through the
  default engine contraction, and built directly at switch level.  The
  contracted solve must report brackets BIT-EQUAL to the switch-level
  build (coarsening is exact — same matrices, same program) while its
  lane is planned at the much smaller switch-only ``padded_n``.
* **aot** — a compile-dominated certified workload run twice in fresh
  subprocesses sharing one fixed cache directory
  (``aotcache.cache_root() / "scale-bench-aot"``, emptied before the
  cold run): the warm process must report ZERO new XLA compiles and well
  under the cold wall.  The children run before this process touches
  JAX — a chip belongs to one process, so a parent holding it would
  starve them.

    PYTHONPATH=src python -m benchmarks.scale_bench [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

import repro
from benchmarks.common import rows_to_csv, write_bench_json
from repro.core import aotcache, traffic
from repro.core.engine import get_engine
from repro.core.vl2 import VL2Spec, vl2_topology

# the BENCH_scale.json contract (tests/test_bench_artifacts.py pins it);
# the tuple fixes the CSV column order, the frozenset is the pinned set
_ROW_ORDER = ("figure", "section", "backend", "label", "n", "padded_n",
              "ok", "wall_s", "mem_gb", "peak_rss_mb", "d_max", "rounds",
              "lb", "ub", "compiles", "hits")
SCALE_ROW_KEYS = frozenset(_ROW_ORDER)
SCALE_EXTRA_KEYS = frozenset({
    "mem_budget_gb", "time_budget_s", "frontier", "coarsen_equal",
    "warm_over_cold", "last_plan",
})

_BACKENDS = ("squaring", "blocked-fw", "ell-bf")

_PROBE_SRC = r"""
import json, resource, sys, time
from repro.core.graphs import random_regular_ell

n, backend = int(sys.argv[1]), sys.argv[2]
g = random_regular_ell(n, 16, seed=0)   # one degree-16 RRG, every backend
t0 = time.perf_counter()
if backend == "ell-bf":
    # the designed at-scale path: padded-ELL tables streamed block by
    # block, no dense [N, N] input ever materialized
    from repro.kernels.ell import ell_bf_apsp_streamed
    _, rounds = ell_bf_apsp_streamed(g.idx, g.wgt, block=min(1024, n))
    extra = {"rounds": int(rounds), "d_max": g.d_max}
else:
    import jax.numpy as jnp
    from repro.core.apsp import apsp
    apsp(jnp.asarray(g.to_dense()), backend).block_until_ready()
    extra = {"rounds": None, "d_max": None}
wall = time.perf_counter() - t0
rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print(json.dumps({"wall_s": wall, "mem_gb": rss_kb / 1e6,
                  "peak_rss_mb": rss_kb / 1e3, **extra}))
"""

_AOT_SRC = r"""
import json, sys, time
from repro.core import aotcache, traffic
from repro.core.engine import get_engine
from repro.core.graphs import random_regular_graph

iters = int(sys.argv[2])
t0 = time.perf_counter()
topos = [random_regular_graph(n, 4, seed=s, servers=3)
         for s, n in enumerate([16, 16, 24, 32])]
dems = [traffic.make("permutation", t.servers, seed=7) for t in topos]
eng = get_engine("certified", iters=iters, aot_cache=sys.argv[1])
res = eng.solve_batch(topos, dems)
out = {"wall_s": time.perf_counter() - t0, "lb": res[0].meta["lb"]}
out.update(aotcache.stats())
print(json.dumps(out))
"""


def _child_env(host: bool = False) -> dict:
    # repro may be a namespace package (__file__ is None): use __path__
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    if host:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _run_child(src: str, argv: list[str], timeout: float,
               host: bool = False) -> dict | None:
    """Run a probe subprocess (``host``: pinned to the CPU); None =
    failed/over-time (the probe's own budget verdict is the caller's
    job)."""
    try:
        out = subprocess.run([sys.executable, "-c", src, *argv],
                             env=_child_env(host), capture_output=True,
                             text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return None
    if out.returncode != 0:
        print(out.stderr[-2000:], file=sys.stderr)
        return None
    return json.loads(out.stdout.strip().splitlines()[-1])


def _row(**kw) -> dict:
    row = dict.fromkeys(_ROW_ORDER)
    row.update(figure="scale", **kw)
    assert set(row) == SCALE_ROW_KEYS
    return row


def _frontier_rows(grid, mem_gb, time_s) -> list[dict]:
    rows = []
    for backend in _BACKENDS:
        for n in grid:
            res = _run_child(_PROBE_SRC, [str(n), backend], timeout=time_s,
                             host=True)
            ok = (res is not None and res["mem_gb"] <= mem_gb
                  and res["wall_s"] <= time_s)
            rows.append(_row(
                section="host-frontier", backend=backend,
                label=f"host-apsp-{n}",
                n=n, ok=bool(ok),
                wall_s=None if res is None else round(res["wall_s"], 3),
                mem_gb=None if res is None else round(res["mem_gb"], 3),
                peak_rss_mb=None if res is None
                else round(res["peak_rss_mb"], 1),
                d_max=None if res is None else res["d_max"],
                rounds=None if res is None else res["rounds"]))
            if not ok:          # cost is monotone in n: stop this backend
                break
    return rows


def _coarsen_rows(spec: VL2Spec, iters: int) -> list[dict]:
    direct = vl2_topology(spec)
    expanded = vl2_topology(spec, server_nodes=True)
    d_sw = traffic.make("permutation", direct.servers, seed=0)
    d_node = traffic.make("permutation", expanded.servers, seed=0)
    eng = get_engine("certified", iters=iters)
    t0 = time.time()
    uncoarse = get_engine("certified", iters=iters,
                          coarsen=False).solve_batch([expanded], [d_node])[0]
    t1 = time.time()
    coarse = eng.solve_batch([expanded], [d_node])[0]
    t2 = time.time()
    ref = eng.solve_batch([direct], [d_sw])[0]
    rows = [
        _row(section="coarsen", backend="auto", label="expanded",
             n=expanded.n, padded_n=uncoarse.meta["padded_n"],
             ok=True, wall_s=round(t1 - t0, 3),
             lb=uncoarse.meta["lb"], ub=uncoarse.meta["ub"]),
        _row(section="coarsen", backend="auto", label="coarsened",
             n=expanded.n, padded_n=coarse.meta["padded_n"],
             ok=coarse.meta["padded_n"] < expanded.n,
             wall_s=round(t2 - t1, 3),
             lb=coarse.meta["lb"], ub=coarse.meta["ub"]),
        _row(section="coarsen", backend="auto", label="switch-level",
             n=direct.n, padded_n=ref.meta["padded_n"], ok=True,
             lb=ref.meta["lb"], ub=ref.meta["ub"]),
    ]
    equal = (coarse.meta["lb"] == ref.meta["lb"]
             and coarse.meta["ub"] == ref.meta["ub"])
    if not equal:
        print("WARNING: coarsened bracket != switch-level bracket",
              file=sys.stderr)
    return rows, equal, eng.last_plan


def _aot_rows(iters: int, timeout: float) -> tuple[list[dict], float | None]:
    rows = []
    d = aotcache.cache_root() / "scale-bench-aot"
    shutil.rmtree(d, ignore_errors=True)   # the first child must be cold
    cold = _run_child(_AOT_SRC, [str(d), str(iters)], timeout=timeout)
    warm = _run_child(_AOT_SRC, [str(d), str(iters)], timeout=timeout)
    ratio = None
    for label, res in (("cold", cold), ("warm", warm)):
        ok = res is not None
        if label == "warm" and ok:
            ok = res["compiles"] == 0 and res["hits"] >= 1
            if cold is not None:
                ratio = res["wall_s"] / cold["wall_s"]
                ok = ok and ratio < 0.5
        rows.append(_row(
            section="aot", backend="auto", label=label, ok=bool(ok),
            wall_s=None if res is None else round(res["wall_s"], 3),
            lb=None if res is None else res["lb"],
            compiles=None if res is None else res["compiles"],
            hits=None if res is None else res["hits"]))
    if warm is not None and warm["compiles"]:
        print("WARNING: warm AOT run recompiled", file=sys.stderr)
    return rows, ratio


def bench(scale: str = "small") -> tuple[list[dict], dict]:
    if scale == "smoke":
        grid, mem_gb, time_s, iters = [256, 512], 1.0, 60.0, 30
        spec = VL2Spec(d_a=4, d_i=4, servers_per_tor=3)
    elif scale == "paper":
        grid = [256, 512, 768, 1024, 2048, 4096, 8192, 16384]
        mem_gb, time_s, iters = 4.0, 600.0, 120
        spec = VL2Spec(d_a=8, d_i=8, servers_per_tor=10)
    else:
        grid = [256, 512, 768, 1024, 2048, 4096, 8192, 16384]
        mem_gb, time_s, iters = 1.5, 150.0, 60
        spec = VL2Spec(d_a=8, d_i=8, servers_per_tor=5)
    # children first: this process touches JAX only in _coarsen_rows
    a_rows, ratio = _aot_rows(iters, timeout=max(time_s, 120.0))
    f_rows = _frontier_rows(grid, mem_gb, time_s)
    frontier = {b: max((r["n"] for r in f_rows if r["backend"] == b
                        and r["ok"]), default=0) for b in _BACKENDS}
    c_rows, equal, last_plan = _coarsen_rows(spec, iters)
    rows = f_rows + c_rows + a_rows
    extra = {"mem_budget_gb": mem_gb, "time_budget_s": time_s,
             "frontier": frontier, "coarsen_equal": bool(equal),
             "warm_over_cold": ratio,
             "last_plan": None if last_plan is None else
             last_plan.as_dict()}
    assert set(extra) == SCALE_EXTRA_KEYS
    return rows, extra


def run(scale: str = "small") -> list[dict]:
    """``benchmarks.run`` entry point: rows only (the generic per-figure
    stats block replaces the scale extra block there)."""
    rows, _ = bench(scale)
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["small", "paper"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI (overrides --scale)")
    args = ap.parse_args()
    t0 = time.time()
    rows, extra = bench("smoke" if args.smoke else args.scale)
    dt = time.time() - t0
    rows_to_csv(rows)
    fr = extra["frontier"]
    head = (f"host ell-bf frontier N={fr['ell-bf']} vs blocked-fw "
            f"N={fr['blocked-fw']} vs squaring N={fr['squaring']} "
            f"under {extra['mem_budget_gb']}GB host RSS")
    if extra["warm_over_cold"] is not None:
        head += f"; warm start {100 * extra['warm_over_cold']:.0f}% of cold"
    path = write_bench_json("scale", rows, headline=head, wall_s=dt,
                            extra=extra)
    print(f"{head}\nwrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()

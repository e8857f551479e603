"""Engine benchmark: the unified ThroughputEngine backends head to head —
exact HiGHS LP vs the JAX dual solver (the CPLEX replacement) vs the
Frank–Wolfe primal solver (certified lower bounds) — accuracy and wall
time, including the batched ``solve_batch`` mode that turns the paper's
'20 runs per point' into one vmapped device program.  Every row reports
the certified bracket the primal+dual pair produces around the exact LP
value.

``--mixed`` benchmarks the ``BatchPlan`` execution core on a heterogeneous
sweep (the Figs. 3-7 shape: many topology sizes, many runs per size) in
four plans: the per-exact-size grouping baseline (one XLA compile per
distinct node count, fixed iterations), the 1-device bucketed plan (one
compile per bucket, early stopping), — when several local devices are
visible, e.g. under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
— the sharded plan (chunked under a lane budget, batch axis sharded over
all devices, async dispatch), and the primal plan (the Frank–Wolfe lower
bound riding the same bucketed/sharded path; its ``compile_keys`` column
shows primal lanes reuse the plan shapes — no per-instance recompiles).
``--smoke`` runs one tiny sweep per registered engine (CI regression
canary).
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import rows_to_csv, write_bench_json
from repro.core import get_engine, graphs, mcf, traffic
from repro.core import plan as plan_mod
from repro.core.engine import DualEngine, PrimalEngine


def run(scale: str = "small") -> list[dict]:
    sizes = [(20, 6), (40, 10)] if scale == "small" else \
        [(20, 6), (40, 10), (80, 10), (120, 12)]
    exact_eng = get_engine("exact")
    dual_eng = get_engine("dual", iters=600)
    primal_eng = get_engine("primal", iters=600)
    rows = []
    for n, r in sizes:
        topo = graphs.random_regular_graph(n, r, seed=1, servers=5)
        dem = traffic.make("permutation", topo.servers, seed=2)
        t0 = time.time()
        exact = exact_eng.solve(topo, dem).throughput
        t_lp = time.time() - t0
        t0 = time.time()
        dual = dual_eng.solve(topo, dem)
        t_dual = time.time() - t0
        t0 = time.time()
        prim = primal_eng.solve(topo, dem)
        t_primal = time.time() - t0
        # batched: 8 instances through one solve_batch (one vmapped program)
        topos = [graphs.random_regular_graph(n, r, seed=s, servers=5)
                 for s in range(8)]
        dems = [traffic.make("permutation", t.servers, seed=s)
                for s, t in enumerate(topos)]
        t0 = time.time()
        dual_eng.solve_batch(topos, dems)
        t_batch = time.time() - t0
        rows.append({
            "figure": "solver", "n": n, "deg": r,
            "exact": exact, "dual_ub": dual.throughput,
            "primal_lb": prim.throughput,
            "gap_pct": 100 * (dual.throughput / exact - 1),
            "lb_gap_pct": 100 * (1 - prim.throughput / exact),
            "bracket_gap_pct":
                100 * (1 - prim.throughput / dual.throughput),
            "lp_s": t_lp, "dual_s": t_dual, "primal_s": t_primal,
            "batch8_s": t_batch, "batch_speedup": 8 * t_dual / t_batch,
        })
    return rows


def _mixed_instances(sizes, runs, deg=10):
    topos, dems = [], []
    for n in sizes:
        for s in range(runs):
            t = graphs.random_regular_graph(n, deg, seed=1000 * n + s,
                                            servers=5)
            topos.append(t)
            dems.append(traffic.make("permutation", t.servers,
                                     seed=1000 * n + s + 1))
    return topos, dems


def run_mixed(scale: str = "small", bucket: str | int | None = 8,
              tol: float = 1e-4, iters: int | None = None,
              devices: int | None = None,
              max_lanes: int | None = None) -> list[dict]:
    """Mixed-size sweep through three ``BatchPlan``s: the pre-bucketing
    baseline (group by exact size, fixed iteration count, one device), the
    1-device bucketed plan (early stopping, one compile per bucket), and —
    with >1 visible device — the sharded plan (buckets chunked under
    ``max_lanes``, each chunk's batch axis sharded over ``devices``, all
    chunks dispatched asynchronously).  Every plan is spot-checked for
    bound quality against per-instance ``solve_dual`` at the full
    iteration cap on a subsample of instances (not part of the timing).

    Bucket granularity trades compile count against padding flops: on CPU
    (where the padded (min,+) work is real) a fine granularity like 16 wins;
    on TPU the Pallas kernel pads every instance to 128-multiples internally,
    so coarse ``"pow2"``/``"mult128"`` buckets cost nothing extra and
    maximise compile reuse.  The chunk lane budget adds a second lever: small
    chunks retire as soon as THEIR slowest lane converges instead of waiting
    on the whole bucket's slowest lane, and overlap across devices."""
    import jax

    if scale == "small":
        sizes, runs, iters = list(range(12, 41, 2)), 5, iters or 800
    else:
        sizes, runs, iters = list(range(40, 65, 2)), 20, iters or 800
    topos, dems = _mixed_instances(sizes, runs, deg=8)
    # per-instance references at the full iteration cap, on a subsample
    # (full references would dwarf the benchmark itself)
    step = max(1, len(topos) // 12)
    ref_idx = list(range(0, len(topos), step))
    refs = {i: mcf.solve_dual(topos[i], dems[i], iters=iters).throughput_ub
            for i in ref_idx}
    ndev = devices or len(jax.local_devices())
    modes = [
        ("per-size", DualEngine, dict(bucket=None, tol=0.0, devices=1)),
        ("bucketed-1dev", DualEngine, dict(bucket=bucket, tol=tol,
                                           devices=1)),
    ]
    if ndev > 1:
        # one lane per device: the smallest chunk shape — cheapest compiles,
        # earliest per-chunk retirement, still a full-width device launch
        modes.append(("sharded", DualEngine,
                      dict(bucket=bucket, tol=tol, devices=ndev,
                           max_lanes=max_lanes or ndev)))
        modes.append(("primal-sharded", PrimalEngine,
                      dict(bucket=bucket, tol=tol, devices=ndev,
                           max_lanes=max_lanes or ndev)))
    else:
        # primal lower bounds through the same bucketed plan shapes as the
        # dual — its compile_keys/compiles columns pin "no per-instance
        # recompiles" for the FW path too
        modes.append(("primal-1dev", PrimalEngine,
                      dict(bucket=bucket, tol=tol, devices=1)))
    rows = []
    for label, cls, kw in modes:
        eng = cls(iters=iters, **kw)
        cache_key = f"{eng.solver}.solve_batch"
        c0 = plan_mod.compile_cache_sizes()[cache_key]
        t0 = time.time()
        out = eng.solve_batch(topos, dems)
        wall = time.time() - t0
        c1 = plan_mod.compile_cache_sizes()[cache_key]
        compiles = c1 - c0 if c0 is not None and c1 is not None else None
        if eng.solver == "primal":
            # max_rel_dev = worst certified bracket gap vs the dual refs
            assert all(out[i].throughput <= refs[i] * (1 + 1e-4)
                       for i in ref_idx), "primal lb must stay below dual ub"
            dev = max(1 - out[i].throughput / refs[i] for i in ref_idx)
        else:
            dev = max(abs(out[i].throughput / refs[i] - 1) for i in ref_idx)
        plan = eng.last_plan
        mean_iters = float(np.mean([r.meta["iterations"] for r in out]))
        rows.append({
            "figure": "solver_mixed", "mode": label, "instances": len(topos),
            "distinct_sizes": len(sizes), "buckets": plan.buckets,
            "chunks": plan.chunks, "devices": plan.devices,
            "compile_keys": len(plan.compile_keys), "compiles": compiles,
            "wall_s": wall, "mean_iters": mean_iters, "max_rel_dev": dev,
        })
    base, plan_1dev = rows[0], rows[1]
    for r in rows:
        r["speedup_vs_per_size"] = base["wall_s"] / r["wall_s"]
        # the headline number: every plan vs the 1-device bucketed plan
        # (for the sharded row this is the multi-device speedup)
        r["speedup_vs_1dev_plan"] = plan_1dev["wall_s"] / r["wall_s"]
    return rows


def run_smoke() -> list[dict]:
    """One tiny mixed-size sweep per engine — fails fast on engine-registry
    or batching regressions (used by CI).  Also crosses the Pallas (min,+)
    kernel itself once in interpret mode: the sweep instances below are
    small enough to take the reference fallback inside
    ``ops.minplus_matmul``, so without this a kernel regression would slip
    past the smoke."""
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    w = jnp.where(jnp.eye(128, dtype=bool), 0.0, 1.0)
    d2 = np.asarray(kops.minplus_matmul(w, w, 128, True))
    assert np.allclose(np.diag(d2), 0.0) and np.allclose(
        d2[~np.eye(128, dtype=bool)], 1.0), "pallas minplus kernel broken"

    topos, dems = _mixed_instances([12, 16], runs=5, deg=4)
    engines = [
        get_engine("exact"),
        get_engine("dual", iters=60, tol=1e-3),
        get_engine("dual", backend="squaring-pallas", iters=60, tol=1e-3,
                   interpret=True),
        get_engine("primal", iters=60, tol=1e-3),
        get_engine("certified", iters=60, tol=1e-3),
    ]
    import jax
    multi_dev = len(jax.local_devices()) > 1
    if multi_dev:
        # exercise the sharded MULTI-chunk BatchPlan path too (CI runs this
        # under XLA_FLAGS=--xla_force_host_platform_device_count=8; the 10
        # instances above split into >= 2 chunks at one lane per device)
        engines.append(get_engine("dual", iters=60, tol=1e-3,
                                  max_lanes=2))
    rows = []
    for eng in engines:
        t0 = time.time()
        out = eng.solve_batch(topos, dems)
        assert len(out) == len(topos)
        assert all(r.throughput > 0 and r.engine == eng.name for r in out)
        if eng.name == "certified":
            assert all(0 <= r.meta["lb"] <= r.meta["ub"] and
                       np.isfinite(r.meta["gap"]) for r in out), \
                "certified smoke must produce finite brackets"
        plan = getattr(eng, "last_plan", None)
        rows.append({"figure": "solver_smoke", "engine": eng.name,
                     "backend": getattr(eng, "backend", None),
                     "instances": len(out), "wall_s": time.time() - t0,
                     "devices": plan.devices if plan else 1,
                     "chunks": plan.chunks if plan else 0,
                     "mean_throughput":
                         float(np.mean([r.throughput for r in out]))})
    if multi_dev and len(jax.local_devices()) < len(topos):
        # with fewer devices than instances the lane budget must split the
        # bucket; with >= len(topos) devices one chunk holds everything
        assert rows[-1]["chunks"] > 1, \
            "sharded smoke engine must dispatch multiple chunks"
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", default="small", choices=["small", "paper"])
    ap.add_argument("--bucket", default="8",
                    help="bucket mode for --mixed: pow2|mult128|<int>|none "
                         "(fine int granularity suits CPU; pow2/mult128 "
                         "suit accelerators)")
    ap.add_argument("--tol", type=float, default=1e-4,
                    help="early-stop relative-improvement tolerance for the "
                         "bucketed --mixed mode (0 = off)")
    ap.add_argument("--devices", type=int, default=None,
                    help="devices for the sharded --mixed plan "
                         "(default: all local devices)")
    ap.add_argument("--max-lanes", type=int, default=None,
                    help="chunk lane budget for the sharded --mixed plan "
                         "(default: one lane per device)")
    ap.add_argument("--mixed", action="store_true",
                    help="run the mixed-size BatchPlan benchmark "
                         "(per-size vs bucketed vs sharded)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the tiny per-engine CI smoke sweep")
    args = ap.parse_args()
    bucket = args.bucket if not args.bucket.isdigit() else int(args.bucket)
    t0 = time.time()
    if args.smoke:
        name, rows = "solver_smoke", run_smoke()
    elif args.mixed:
        name, rows = "solver_mixed", run_mixed(args.scale, bucket, args.tol,
                                               devices=args.devices,
                                               max_lanes=args.max_lanes)
    else:
        name, rows = "solver", run(args.scale)
    rows_to_csv(rows)
    path = write_bench_json(name, rows, wall_s=time.time() - t0,
                            extra={"compiles":
                                   plan_mod.compile_cache_sizes()})
    print(f"wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()

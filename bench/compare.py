"""The comparison that decides ``correct``.

Every bracket of the window must be one: ``0 <= lb <= ub < inf`` after
the solver's iteration budget (all ``iters`` when ``tol`` is 0, at most
``iters`` otherwise), with ``lb = 0`` exactly when
some demanded pair has no path (then theta* = 0; the benchmark finds the
fabric's components itself), and ``lb > 0`` otherwise (``bad_brackets``,
limit 0).  A sample of the window's brackets, drawn from the run's seed,
is recomputed by the plain reference (``bench/reference.py``) on the same
instances, and for those whose theta* > 0:

* ``ub_rel_diff`` and ``lb_rel_diff``: the widest relative gap between
  the system's upper (lower) bound and the reference's;
* ``lb_over_ref_ub``: how far the system's lower bound lies above the
  reference's upper bound, relative to it (0 when below).  A certified
  lower bound can never exceed a certified upper bound, so its limit is
  the float32 resolution.

Each limit is in the workload file, beside the readings it was set from
(``PERF.md``).
"""
from __future__ import annotations

import math

import numpy as np

from bench import gen, reference

SAMPLE_KEY = 0x5EED


def sample(run) -> list[int]:
    """Indices of the window's brackets the reference recomputes."""
    total = sum(len(c["lanes"]) for c in run.calls)
    k = min(total, run.wl["reference"]["lanes"])
    pick = gen.rng_for(run.seed, SAMPLE_KEY).choice(total, k, replace=False)
    return sorted(int(i) for i in pick)


NAMES = ("ub_rel_diff", "lb_rel_diff", "lb_over_ref_ub")


def routable(lane: dict) -> bool:
    """Whether every demanded pair of the lane's fabric is connected
    (cached in the lane)."""
    if "routable" not in lane:
        adj = lane["cap"] > 0
        label = np.full(len(adj), -1)
        for root in range(len(adj)):
            if label[root] >= 0:
                continue
            label[root] = root
            frontier = np.zeros(len(adj), bool)
            frontier[root] = True
            while frontier.any():
                nxt = adj[frontier].any(axis=0) & (label < 0)
                label[nxt] = root
                frontier = nxt
        src, dst = np.nonzero(lane["dem"] > 0)
        lane["routable"] = bool((label[src] == label[dst]).all())
    return lane["routable"]


def numbers(lb, ub, ref) -> dict[str, np.ndarray]:
    """Per bracket, each compared number against ``ref[:, (lb, ub)]``."""
    lb, ub = np.asarray(lb, np.float64), np.asarray(ub, np.float64)
    return {"ub_rel_diff": np.abs(ub - ref[:, 1]) / ref[:, 1],
            "lb_rel_diff": np.abs(lb - ref[:, 0]) / ref[:, 0],
            "lb_over_ref_ub": np.maximum(0.0, (lb - ref[:, 1]) / ref[:, 1])}


def budget_kept(iterations: int, solver: dict) -> bool:
    """Whether a bracket ran the iterations the solver's budget gives."""
    if solver["tol"] > 0:
        return 0 < iterations <= solver["iters"]
    return iterations == solver["iters"]


def reference_brackets(run, lanes: list[dict], **kw) -> np.ndarray:
    """The reference's (lb, ub) of ``lanes`` under the run's solver."""
    s = run.solver
    return reference.brackets([x["cap"] for x in lanes],
                              [x["dem"] for x in lanes], iters=s["iters"],
                              lr=s["lr"], tol=s["tol"],
                              check_every=s["check_every"],
                              block=run.wl["reference"]["block"], **kw)


def check(run) -> tuple[dict, int]:
    """(checks, failed): each number compared with its limit, and the
    number of brackets that failed a check."""
    limits = run.wl["limits"]
    lanes = [lane for c in run.calls for lane in c["lanes"]]
    bad = [not (0.0 <= lane["lb"] <= lane["ub"] < math.inf)
           or not budget_kept(lane["iterations"], run.solver)
           or (lane["lb"] > 0.0) != routable(lane) for lane in lanes]
    idx = [i for i in sample(run) if routable(lanes[i])]
    picked = [lanes[i] for i in idx]
    got = reference_brackets(run, picked)
    got_numbers = numbers([x["lb"] for x in picked],
                          [x["ub"] for x in picked], got)
    checks = {"bad_brackets": {"value": int(sum(bad)),
                               "limit": limits["bad_brackets"]}}
    wrong = np.zeros(len(picked), bool)
    for name, per_lane in got_numbers.items():
        checks[name] = {"value": float(per_lane.max(initial=0.0)),
                        "limit": limits[name]}
        wrong |= per_lane > limits[name]
    for i, w in zip(idx, wrong):
        bad[i] = bad[i] or bool(w)
    return checks, int(sum(bad))

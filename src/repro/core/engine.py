"""Unified throughput engines + the declarative sweep runner.

Every figure in the paper is the same experiment: build a topology, pick a
traffic matrix, measure max-concurrent-flow throughput, repeat over seeds.
This module gives that one API:

* ``ThroughputEngine`` — the protocol every solver backend implements:
  ``solve(topo, dem) -> ThroughputResult`` and a same-length
  ``solve_batch(topos, dems)``.
* ``ExactLPEngine`` — the HiGHS LP oracle (``repro.core.lp``); exact but
  sequential.
* ``DualEngine`` — the JAX dual solver (``repro.core.mcf``); a certified
  upper bound that converges to the optimum.  Its ``solve_batch`` delegates
  to the ``repro.core.plan.BatchPlan`` execution core: instances are
  grouped into size *buckets* (powers of two by default), each bucket is
  split into chunks under a ``max_lanes`` budget, every chunk's batch axis
  is sharded across ``devices`` local devices, and all chunks dispatch
  asynchronously with ONE host sync at the end — a whole mixed-size sweep
  compiles once per (bucket, chunk-shape) and keeps every device busy.
  ``backend`` picks the APSP backend (``repro.core.apsp``;
  ``"squaring-pallas"`` runs the Pallas (min,+) kernel at every size);
  ``interpret=None`` auto-detects
  compiled-vs-interpreter from the JAX backend.  ``tol > 0`` enables
  convergence-based early stopping.
* ``PrimalEngine`` — the Frank–Wolfe primal solver (``repro.core.primal``);
  a certified LOWER bound from an explicit feasible flow.  Same planner,
  same knobs: primal lanes ride the same buckets/chunks/sharding.
* ``CertifiedEngine`` — the fused bracket engine: one primal program per
  lane computes both the FW lower bound and the dual descent's upper bound
  through one ``BatchPlan``, and every result carries ``meta["lb"]`` /
  ``meta["ub"]`` / ``meta["gap"]``.
* ``EcmpEngine`` / ``KspEngine`` — routing-restricted lower bounds
  (``repro.core.routing``): deployable throughput under ECMP and
  k-shortest-path multipath routing, each carrying the ideal bracket's
  upper bound and ``meta["ideal_gap_pct"]`` (the certified price of the
  routing restriction).
* ``get_engine("exact" | "dual" | "primal" | "certified" | "ecmp" |
  "ksp" | "auto" | "adversarial")`` — string registry; ``as_engine``
  additionally passes engine instances through, so every driver accepts
  either.
* ``Sweep`` / ``run_sweep`` / ``run_sweeps`` — declarative (xs × runs)
  experiments: a build function, a named traffic pattern, and an engine.
  ``run_sweeps`` routes EVERY instance of a whole figure family (many
  sweeps) through one ``solve_batch`` call — i.e. one ``BatchPlan`` on
  batching engines — and aggregates brackets (``lb_mean``/``gap_max``)
  into each ``SweepPoint`` when the engine provides them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Protocol, Sequence, runtime_checkable

import numpy as np

from repro.core import adversarial as adversarial_mod
from repro.core import aotcache, lp, mcf, primal, routing, spans
from repro.core import apsp as apsp_mod
from repro.core import traffic as traffic_mod
from repro.core.graphs import Topology, as_cap
from repro.core.plan import (  # noqa: F401  (bucket_size re-exported)
    BatchPlan, InstanceSolve, bucket_size,
)

__all__ = [
    "ThroughputResult",
    "ThroughputEngine",
    "ExactLPEngine",
    "DualEngine",
    "PrimalEngine",
    "CertifiedEngine",
    "EcmpEngine",
    "KspEngine",
    "AutoEngine",
    "AdversarialEngine",
    "ENGINES",
    "get_engine",
    "as_engine",
    "bucket_size",
    "SweepPoint",
    "Sweep",
    "run_sweep",
    "run_sweeps",
]


@dataclasses.dataclass(frozen=True)
class ThroughputResult:
    """Throughput of one (topology, demand) instance, engine-agnostic.

    ``throughput`` is θ, the max concurrent flow rate per unit of demand:
    every entry of ``dem[N, N]`` can be routed simultaneously at rate
    θ·dem[s, t] within the capacities ``cap[N, N]`` (both in units of the
    base line-speed — 1 = one 1GbE link's worth).  θ ≥ 1 means "full
    throughput" in the paper's sense.

    ``bound`` says what kind of claim ``throughput`` is: ``"exact"`` (the
    LP optimum), ``"upper"`` / ``"lower"`` (a certified one-sided bound
    that converges to θ*), or ``"bracket"`` (an upper bound whose ``meta``
    carries the full ``lb``/``ub``/``gap`` bracket).  It defaults from
    ``is_upper_bound`` for backwards compatibility.
    """

    throughput: float        # θ: per-unit-demand max concurrent flow rate
    is_upper_bound: bool     # True: certified bound that converges to θ*
    engine: str              # registry name of the engine that produced it
    meta: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    bound: str = ""          # "exact" | "upper" | "lower" | "bracket"

    def __post_init__(self):
        if not self.bound:
            object.__setattr__(self, "bound",
                               "upper" if self.is_upper_bound else "exact")


@runtime_checkable
class ThroughputEngine(Protocol):
    """Protocol for throughput solver backends.

    ``solve`` takes one ``Topology`` (or a bare symmetric ``cap[N, N]``
    capacity matrix, units of the base line-speed) and a ``dem[N, N]``
    demand matrix (unit-demand flows per switch pair) and returns a
    ``ThroughputResult`` whose ``bound`` field names the certification
    (exact / upper / lower / bracket).  ``solve_batch`` is positional and
    same-length: result ``i`` answers instance ``i``.  ``batches`` is
    True when ``solve_batch`` is cheaper than per-instance ``solve``
    calls (drivers use it to keep early-exit loops on sequential
    engines)."""

    name: str
    batches: bool   # True if solve_batch is cheaper than per-instance solves

    def solve(self, topo: Topology | np.ndarray,
              dem: np.ndarray) -> ThroughputResult: ...

    def solve_batch(self, topos: Sequence[Topology | np.ndarray],
                    dems: Sequence[np.ndarray]) -> list[ThroughputResult]: ...


def _check_batch_lengths(topos, dems) -> None:
    if len(topos) != len(dems):
        raise ValueError(f"topos ({len(topos)}) and dems ({len(dems)}) "
                         "must have equal length")


class ExactLPEngine:
    """Exact max-concurrent-flow via the HiGHS LP (``repro.core.lp``):
    ``bound="exact"`` — the returned θ IS the optimum, no certification
    gap.  Sequential (one LP per instance) and only tractable at small N
    (minutes beyond ~100 nodes); the JAX engines take over from there."""

    name = "exact"
    batches = False

    def solve(self, topo, dem) -> ThroughputResult:
        res = lp.max_concurrent_flow(topo, dem, want_flows=False)
        return ThroughputResult(throughput=res.throughput,
                                is_upper_bound=False, engine=self.name,
                                meta={"status": res.status})

    def solve_batch(self, topos, dems) -> list[ThroughputResult]:
        _check_batch_lengths(topos, dems)
        return [self.solve(t, d) for t, d in zip(topos, dems)]


class _PlannedEngine:
    """Shared planner plumbing of every JAX solver engine.

    ``solve_batch`` delegates to ``repro.core.plan.BatchPlan``: instances
    are grouped into size buckets (``bucket``: ``"pow2"`` by default — see
    ``bucket_size``), each padded to its largest member (an equal-size
    group therefore pads nothing); each bucket is split into chunks of at
    most ``max_lanes`` batch rows (``None`` = the whole bucket in one
    launch; a budget below the device count is raised to one lane per
    device — every launch spans all ``devices``, so that is the floor on
    rows per launch); each chunk's batch axis is sharded over ``devices``
    local devices (``None`` = all of them) and all chunks dispatch
    asynchronously, so a mixed-size sweep triggers one XLA compile per
    (bucket, chunk-shape) and one host sync total.  Results come back in
    input order, each carrying the solver's per-instance outputs plus its
    plan placement (``bucket``/``chunk``/``devices``/``plan`` stats) in
    ``meta``; ``last_plan`` keeps the most recent ``PlanStats``.  ``tol >
    0`` enables per-instance convergence-based early stopping (checked
    every ``check_every`` steps); ``interpret=None`` auto-detects the
    Pallas execution mode from the JAX backend.

    ``on_disconnected`` pins what happens when a demanded (s, t) pair has
    no path (failure scenarios produce these routinely): ``None`` (default)
    solves as-is — the dual ratio legitimately drives the bound toward the
    true θ* = 0 — ``"raise"`` rejects the instance before solving, and
    ``"drop"`` zeroes the unroutable demand, solves the routable remainder
    and reports the zeroed share in ``meta["dropped_demand_fraction"]``
    (0.0 when nothing was dropped).  An instance whose demand is entirely
    unroutable is never dispatched to a solver under ``"drop"``: it
    reports throughput 0 (lb = ub = 0 on bracket engines) with
    ``meta["disconnected"] = True``.

    Subclasses set ``solver`` (the ``plan.SOLVERS`` key) and implement
    ``solve`` plus ``_result`` (how one ``InstanceSolve`` becomes a
    ``ThroughputResult``).
    """

    batches = True
    solver: str = "dual"

    def __init__(self, iters: int = 800, lr: float = 0.08,
                 tol: float = 0.0, check_every: int = 25,
                 bucket: str | int | None = "pow2",
                 interpret: bool | None = None,
                 devices: int | None = None,
                 max_lanes: int | None = None,
                 on_disconnected: str | None = None,
                 backend: str | None = None,
                 coarsen: bool = True,
                 aot_cache: bool | str | None = None,
                 d_max: int | None = None,
                 max_rounds: int | None = None):
        self.iters = iters
        self.lr = lr
        self.tol = tol
        self.check_every = check_every
        bucket_size(1, bucket)   # fail fast on an unknown bucket mode
        self.bucket = bucket
        self.interpret = interpret
        self.devices = devices
        self.max_lanes = max_lanes
        if on_disconnected not in (None, "raise", "drop"):
            raise ValueError("on_disconnected must be None, 'raise' or "
                             f"'drop', got {on_disconnected!r}")
        self.on_disconnected = on_disconnected
        # backend: ApspBackend registry name (None -> "auto")
        self.backend = apsp_mod.normalize_backend(backend)
        # coarsen: contract server leaf nodes (Topology.server_nodes) onto
        # their switches before planning, so plan lanes carry switch-only
        # graphs with lifted demand (exact; see Topology.coarsen)
        self.coarsen = coarsen
        # aot_cache: persistent ahead-of-time compile cache.  None defers
        # to $REPRO_AOT_CACHE; True uses the default cache dir; a string
        # is the cache dir itself.  Off by default.
        self._aot = aotcache.resolve(aot_cache)
        # d_max / max_rounds: ell-bf statics (table width / relaxation-round
        # cap).  None lets BatchPlan.execute compute per-chunk density hints
        # from the unpadded members (see plan._density_hints).
        self.d_max = d_max
        self.max_rounds = max_rounds
        self.last_plan = None    # PlanStats of the most recent solve_batch

    def _solver_kw(self) -> dict:
        kw = dict(iters=self.iters, lr=self.lr, tol=self.tol,
                  check_every=self.check_every, backend=self.backend,
                  interpret=self.interpret, aot=self._aot)
        # only pin the ell-bf statics when set, so the planner's per-chunk
        # density hints stay in charge otherwise
        if self.d_max is not None:
            kw["d_max"] = self.d_max
        if self.max_rounds is not None:
            kw["max_rounds"] = self.max_rounds
        return kw

    def _coarsen_instances(self, topos, dems):
        """Contract server-expanded topologies (``server_nodes`` marked)
        onto switch-only graphs with lifted demand.  Instances without
        server nodes pass through untouched."""
        if not self.coarsen:
            return list(topos), list(dems)
        out_t, out_d = [], []
        for t, d in zip(topos, dems):
            if isinstance(t, Topology) and t.server_nodes is not None:
                t, d = t.coarsen(d)
            out_t.append(t)
            out_d.append(d)
        return out_t, out_d

    def plan(self, topos, dems) -> BatchPlan:
        """The ``BatchPlan`` this engine would execute for these instances
        (exposed for introspection and tests)."""
        _check_batch_lengths(topos, dems)
        topos, dems = self._coarsen_instances(topos, dems)
        return BatchPlan.build(topos, dems, bucket=self.bucket,
                               max_lanes=self.max_lanes,
                               devices=self.devices)

    def _apply_disconnection_policy(self, topos, dems):
        """Apply ``on_disconnected`` to one pile: returns (dems, dropped)
        where ``dropped[i]`` is the zeroed demand share (None on the
        pass-through policy).  ``dropped[i] == 1.0`` marks an instance
        that must not reach a solver (no routable demand at all)."""
        if self.on_disconnected is None:
            return list(dems), [None] * len(dems)
        kept, dropped = [], []
        for i, (t, d) in enumerate(zip(topos, dems)):
            d2, frac = mcf.drop_disconnected(as_cap(t), d)
            if frac > 0 and self.on_disconnected == "raise":
                raise ValueError(
                    f"instance {i}: {100 * frac:.1f}% of the demand is "
                    "between disconnected switches; use "
                    "on_disconnected='drop' to solve the routable share")
            kept.append(d2)
            dropped.append(frac)
        return kept, dropped

    def _disconnected_result(self) -> ThroughputResult:
        """The fully-unroutable instance: θ* = 0 by definition, certified
        on both sides without running a solver."""
        s = InstanceSolve(value=0.0, iterations=0,
                          meta={"ub": 0.0, "final_ratio": 0.0,
                                "final_util": 0.0, "disconnected": True})
        return self._result(s)

    @staticmethod
    def _with_dropped(r: ThroughputResult,
                      frac: float | None) -> ThroughputResult:
        if frac is None:
            return r
        return dataclasses.replace(
            r, meta={**r.meta, "dropped_demand_fraction": frac})

    def _solve_preprocessed(self, topo, dem):
        """One-instance coarsen + ``on_disconnected`` preamble for
        ``solve``: (topo, kept_dem, dropped_fraction,
        short_circuit_result_or_None)."""
        (topo,), (dem,) = self._coarsen_instances([topo], [dem])
        dems, dropped = self._apply_disconnection_policy([topo], [dem])
        frac = dropped[0]
        if frac is not None and frac >= 1.0:
            return topo, dems[0], frac, self._with_dropped(
                self._disconnected_result(), frac)
        return topo, dems[0], frac, None

    @spans.span("engine.solve_batch")
    def solve_batch(self, topos, dems) -> list[ThroughputResult]:
        _check_batch_lengths(topos, dems)
        with spans.span("engine.prepare"):
            topos, dems = self._coarsen_instances(topos, dems)
            dems, dropped = self._apply_disconnection_policy(topos, dems)
        live = [i for i, f in enumerate(dropped) if f is None or f < 1.0]
        plan = self.plan([topos[i] for i in live], [dems[i] for i in live])
        self.last_plan = plan.stats
        solved = plan.execute(solver=self.solver, **self._solver_kw())
        out: list[ThroughputResult] = [self._disconnected_result()
                                       for _ in topos]
        for i, s in zip(live, solved):
            out[i] = self._result(s)
        return [self._with_dropped(r, f) for r, f in zip(out, dropped)]


class DualEngine(_PlannedEngine):
    """Certified dual UPPER bound via JAX (``repro.core.mcf``):
    ``bound="upper"`` — θ* ≤ ``throughput`` at every iterate, converging
    to θ* as the descent proceeds.  Batchable through the ``BatchPlan``
    execution core (see ``_PlannedEngine``); ``meta`` carries
    ``iterations`` and ``final_ratio`` (the last iterate's bound — its
    distance from ``throughput`` is a convergence probe)."""

    name = "dual"
    solver = "dual"

    def solve(self, topo, dem) -> ThroughputResult:
        topo, dem, frac, short = self._solve_preprocessed(topo, dem)
        if short is not None:
            return short
        res = mcf.solve_dual(topo, dem, **self._solver_kw())
        return self._with_dropped(ThroughputResult(
            throughput=res.throughput_ub, is_upper_bound=True,
            engine=self.name,
            meta={"iterations": res.iterations,
                  "final_ratio": res.final_ratio}), frac)

    def _result(self, s) -> ThroughputResult:
        return ThroughputResult(throughput=s.value, is_upper_bound=True,
                                engine=self.name, meta=s.meta)


class PrimalEngine(_PlannedEngine):
    """Certified primal LOWER bound via Frank–Wolfe shortest-path routing
    (``repro.core.primal``): ``bound="lower"`` — an explicit feasible
    flow routes every demand at rate ``throughput``, so θ* ≥
    ``throughput`` is a constructive proof.  The driving dual descent's
    free upper bound rides along in ``meta["ub"]``.  Same planner, same
    knobs as ``DualEngine`` — primal lanes reuse the same
    buckets/chunks/device sharding."""

    name = "primal"
    solver = "primal"

    def solve(self, topo, dem) -> ThroughputResult:
        topo, dem, frac, short = self._solve_preprocessed(topo, dem)
        if short is not None:
            return short
        res = primal.solve_primal(topo, dem, **self._solver_kw())
        return self._with_dropped(ThroughputResult(
            throughput=res.throughput_lb, is_upper_bound=False,
            engine=self.name, bound="lower",
            meta={"iterations": res.iterations,
                  "final_util": res.final_util,
                  "ub": res.throughput_ub}), frac)

    def _result(self, s) -> ThroughputResult:
        return ThroughputResult(throughput=s.value, is_upper_bound=False,
                                engine=self.name, bound="lower", meta=s.meta)


def _bracket(lb: float, ub: float, meta: Mapping[str, Any],
             engine: str) -> ThroughputResult:
    gap = (ub - lb) / max(ub, 1e-30)
    meta = {k: v for k, v in meta.items() if k != "ub"}
    return ThroughputResult(
        throughput=ub, is_upper_bound=True, engine=engine, bound="bracket",
        meta={"lb": lb, "ub": ub, "gap": gap, **meta})


class CertifiedEngine(PrimalEngine):
    """Certified (lb, ub, gap) brackets from ONE fused program per lane:
    ``bound="bracket"`` — lb ≤ θ* ≤ ub is provable, with ``gap`` =
    (ub−lb)/ub the relative width.  The Frank–Wolfe primal average
    (lower bound) and the dual descent it rides on (upper bound) share
    each iteration's APSP forward+backward, so dual+primal run through
    one ``BatchPlan`` at roughly the cost of either alone.
    ``throughput`` is the upper bound (it converges to θ*);
    ``meta["lb"]``/``meta["ub"]``/``meta["gap"]`` carry the bracket —
    pass/fail criteria should judge ``meta["lb"]`` (what
    ``vl2.supports_full_throughput`` does)."""

    name = "certified"

    def solve(self, topo, dem) -> ThroughputResult:
        topo, dem, frac, short = self._solve_preprocessed(topo, dem)
        if short is not None:
            return short
        res = primal.solve_primal(topo, dem, **self._solver_kw())
        return self._with_dropped(
            _bracket(res.throughput_lb, res.throughput_ub,
                     {"iterations": res.iterations,
                      "final_util": res.final_util}, self.name), frac)

    def _result(self, s) -> ThroughputResult:
        return _bracket(s.value, s.meta["ub"], s.meta, self.name)


def _ideal_gap_pct(lb: float, ub: float) -> float:
    """Certified price of a routing restriction, in percent of the ideal
    upper bound (0.0 on degenerate ub <= 0 instances)."""
    return 100.0 * (ub - lb) / ub if ub > 0 else 0.0


class EcmpEngine(_PlannedEngine):
    """Routing-restricted LOWER bound under ECMP (``repro.core.routing``):
    ``bound="lower"`` — an explicit equal-cost equal-split routing
    carries every demand at rate ``throughput``, so the deployable
    throughput under the routing operators actually run is >=
    ``throughput``.  The fused ideal dual descent's upper bound rides
    along in ``meta["ub"]`` and ``meta["ideal_gap_pct"]`` reports the
    certified price of the restriction (the Jellyfish gap).  Same
    planner, same knobs as ``DualEngine`` plus ``hops`` (fixed-point
    propagation depth; default N always covers the diameter)."""

    name = "ecmp"
    solver = "ecmp"
    _single = staticmethod(routing.solve_ecmp)

    def __init__(self, hops: int | None = None, **kw):
        super().__init__(**kw)
        self.hops = hops

    def _solver_kw(self) -> dict:
        kw = super()._solver_kw()
        if self.hops is not None:
            kw["hops"] = self.hops
        return kw

    def solve(self, topo, dem) -> ThroughputResult:
        topo, dem, frac, short = self._solve_preprocessed(topo, dem)
        if short is not None:
            return short
        res = self._single(topo, dem, **self._solver_kw())
        s = InstanceSolve(value=res.throughput_lb, iterations=res.iterations,
                          meta={"iterations": res.iterations,
                                "final_util": res.final_util,
                                "ub": res.throughput_ub})
        return self._with_dropped(self._result(s), frac)

    def _result(self, s) -> ThroughputResult:
        meta = {**s.meta,
                "ideal_gap_pct": _ideal_gap_pct(s.value, s.meta["ub"])}
        return ThroughputResult(throughput=s.value, is_upper_bound=False,
                                engine=self.name, bound="lower", meta=meta)


class KspEngine(EcmpEngine):
    """Routing-restricted LOWER bound under k-shortest-path multipath
    routing (``repro.core.routing``): multiplicative weights over each
    pair's ``k`` shortest simple paths, floored by the ECMP baseline it
    deviates from — so ``ecmp <= ksp(k) <= exact`` holds mechanically
    (see the routing module docstring).  Knobs: ``k`` (paths per pair,
    default 8) and ``max_hops`` (per-path hop budget; default
    min(N-1, 12), resolved from the padded width so refill rounds share
    compile keys); ``meta`` matches ``EcmpEngine``'s."""

    name = "ksp"
    solver = "ksp"
    _single = staticmethod(routing.solve_ksp)

    def __init__(self, k: int = routing.DEFAULT_K,
                 max_hops: int | None = None, **kw):
        super().__init__(**kw)
        self.k = k
        self.max_hops = max_hops

    def _solver_kw(self) -> dict:
        kw = super()._solver_kw()
        kw["k"] = self.k
        if self.max_hops is not None:
            kw["max_hops"] = self.max_hops
        return kw


class AutoEngine:
    """Exact LP for small instances, dual bound beyond ``exact_max_nodes``
    — so a mixed batch returns ``bound="exact"`` results for small
    instances and ``bound="upper"`` beyond the threshold (check
    per-result ``bound``, not the engine name).

    ``dual_kw`` (including the planner knobs ``devices``/``max_lanes``/
    ``bucket``) forwards to the inner ``DualEngine``; the dual share of a
    batch goes through one ``BatchPlan`` (``last_plan`` proxies its stats).
    """

    name = "auto"
    batches = True

    def __init__(self, exact_max_nodes: int = 64, **dual_kw):
        self.exact_max_nodes = exact_max_nodes
        self._exact = ExactLPEngine()
        self._dual = DualEngine(**dual_kw)

    @property
    def devices(self) -> int | None:
        return self._dual.devices

    @property
    def max_lanes(self) -> int | None:
        return self._dual.max_lanes

    @property
    def last_plan(self):
        return self._dual.last_plan

    def _pick(self, topo) -> ThroughputEngine:
        n = as_cap(topo).shape[0]
        return self._exact if n <= self.exact_max_nodes else self._dual

    def solve(self, topo, dem) -> ThroughputResult:
        return self._pick(topo).solve(topo, dem)

    def solve_batch(self, topos, dems) -> list[ThroughputResult]:
        _check_batch_lengths(topos, dems)
        exact_idx: list[int] = []
        dual_idx: list[int] = []
        for i, t in enumerate(topos):
            (exact_idx if self._pick(t) is self._exact
             else dual_idx).append(i)
        out: list[ThroughputResult | None] = [None] * len(topos)
        for eng, idx in ((self._exact, exact_idx), (self._dual, dual_idx)):
            if idx:
                sub = eng.solve_batch([topos[i] for i in idx],
                                      [dems[i] for i in idx])
                for i, r in zip(idx, sub):
                    out[i] = r
        return out


class AdversarialEngine:
    """Worst-case-traffic evaluation: ``solve(topo, dem)`` IGNORES the
    usual "score this demand" contract and instead searches the hose
    polytope for the demand that minimises the topology's throughput
    (``repro.core.adversarial.find_worst_tm``), using ``dem`` (when
    given) as the fixed uniform baseline in lane 0 of every search
    round.  ``bound="bracket"``: ``throughput`` is the certified dual
    upper bound of the WORST TM found, ``meta`` carries the full
    certificate — ``lb``/``ub``/``gap`` for that TM, the TM itself
    (``meta["tm"]``), the baseline's bracket, and
    ``meta["uniform_gap_pct"]`` (how much certified headroom the
    adversary destroyed relative to the baseline).

    Ctor kwargs forward to ``find_worst_tm`` (``rounds``,
    ``candidates``, ``lr_tm``, the inner dual-solver knobs, planner
    knobs).  ``batches=False``: each topology runs its own multi-round
    search — batching happens INSIDE a search (one ``BatchPlan.execute``
    over the candidate fleet per round), not across topologies."""

    name = "adversarial"
    batches = False

    def __init__(self, **search_kw):
        self.search_kw = search_kw

    def solve(self, topo, dem=None, *, seed: int = 0) -> ThroughputResult:
        res = adversarial_mod.find_worst_tm(
            topo, seed=seed, baseline=dem, **self.search_kw)
        return _bracket(res.lb, res.ub,
                        {"tm": res.tm,
                         "uniform_gap_pct": res.uniform_gap_pct,
                         "baseline_lb": res.baseline_lb,
                         "baseline_ub": res.baseline_ub,
                         **res.stats}, self.name)

    def solve_batch(self, topos, dems) -> list[ThroughputResult]:
        _check_batch_lengths(topos, dems)
        return [self.solve(t, d) for t, d in zip(topos, dems)]


ENGINES: dict[str, Callable[[], ThroughputEngine]] = {
    "exact": ExactLPEngine,
    "dual": DualEngine,
    "primal": PrimalEngine,
    "certified": CertifiedEngine,
    "ecmp": EcmpEngine,
    "ksp": KspEngine,
    "auto": AutoEngine,
    "adversarial": AdversarialEngine,
}


def get_engine(name: str, **kw) -> ThroughputEngine:
    """Instantiate a registered engine by name (kwargs go to its ctor)."""
    try:
        factory = ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; known: {sorted(ENGINES)}") from None
    return factory(**kw) if kw else factory()


def as_engine(engine: str | ThroughputEngine) -> ThroughputEngine:
    """Accept an engine instance or a registry name (deprecation shim for
    the old ``engine: str`` plumbing)."""
    if isinstance(engine, str):
        return get_engine(engine)
    return engine


# ---------------------------------------------------------------------------
# declarative sweeps
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One x of a sweep: throughput stats over the seeded runs, plus the
    certified bracket aggregates when the engine provides brackets
    (``lb_mean`` = mean certified lower bound, ``gap_max`` = worst
    relative bracket width (ub-lb)/ub across the runs; ``None`` on
    engines without brackets).  ``meta`` carries engine-specific
    aggregates requested via ``run_sweeps(..., meta_reduce=...)`` —
    e.g. the routing engines' ``ideal_gap_pct`` — and is empty when no
    reduction was requested."""

    x: float
    mean: float
    std: float
    values: tuple[float, ...]
    lb_mean: float | None = None
    gap_max: float | None = None
    meta: Mapping[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Sweep:
    """One paper-style experiment: measure throughput at each ``x`` over
    ``runs`` seeded repetitions under a named traffic pattern."""

    xs: tuple[float, ...]
    runs: int = 3
    seed0: int = 0
    traffic: str = "permutation"
    traffic_kw: Mapping[str, Any] = dataclasses.field(default_factory=dict)

    def seeds(self) -> list[int]:
        return [self.seed0 + 1000 * rr for rr in range(self.runs)]


def run_sweeps(items: Sequence[tuple[Sweep, Callable[[float, int], Topology]]],
               engine: str | ThroughputEngine = "exact", *,
               meta_reduce: Mapping[str, Callable[[Sequence[float]], float]]
               | None = None) -> list[list[SweepPoint]]:
    """Run a whole family of sweeps through ONE ``solve_batch`` call.

    ``items`` is a sequence of ``(sweep, build_fn)`` pairs
    (``build_fn(x, seed) -> Topology``; the traffic pattern is drawn with
    seed ``seed + 1`` from each sweep's ``traffic``).  Every (sweep × x ×
    run) instance is built up front and solved in a single batch — on
    batching engines that is one ``BatchPlan`` spanning the entire figure
    family (Fig. 6's grid, Fig. 7's three panels, ...), so bucketing,
    chunking and device sharding see ALL the work at once.  Returns one
    ``list[SweepPoint]`` per input item, in order.

    ``meta_reduce`` maps engine-specific meta keys to reducers (e.g.
    ``{"ideal_gap_pct": max}``): each key present in EVERY run of a
    point is reduced over the point's runs into ``SweepPoint.meta``
    (keys missing from any run are skipped, so a reduction requested for
    one engine is harmless on another).  The built-in bracket aggregates
    (``lb_mean``/``gap_max``) are computed exactly as before, with or
    without the hook.
    """
    eng = as_engine(engine)
    with spans.span("engine.run_sweeps"):
        topos, dems, starts = [], [], []
        with spans.span("sweep.build"):
            for sweep, build_fn in items:
                start = len(topos)
                for x in sweep.xs:
                    for seed in sweep.seeds():
                        topo = build_fn(x, seed)
                        dem = traffic_mod.make(sweep.traffic, topo.servers,
                                               seed + 1, **sweep.traffic_kw)
                        topos.append(topo)
                        dems.append(dem)
                starts.append(start)
        results = eng.solve_batch(topos, dems) if topos else []
    out: list[list[SweepPoint]] = []
    for (sweep, _), start in zip(items, starts):
        points = []
        for pi, x in enumerate(sweep.xs):
            lo = start + pi * sweep.runs
            rs = results[lo:lo + sweep.runs]
            vals = [r.throughput for r in rs]
            v = np.asarray(vals)
            # brackets ride along when every run of the point carries one
            lbs = [r.meta["lb"] for r in rs if "lb" in r.meta]
            gaps = [r.meta["gap"] for r in rs if "gap" in r.meta]
            bracketed = rs and len(lbs) == len(rs) and len(gaps) == len(rs)
            meta: dict[str, float] = {}
            for key, reduce_fn in (meta_reduce or {}).items():
                got = [r.meta[key] for r in rs if key in r.meta]
                if rs and len(got) == len(rs):
                    meta[key] = float(reduce_fn(got))
            points.append(SweepPoint(
                float(x), float(v.mean()), float(v.std()), tuple(vals),
                lb_mean=float(np.mean(lbs)) if bracketed else None,
                gap_max=float(max(gaps)) if bracketed else None,
                meta=meta))
        out.append(points)
    return out


def run_sweep(sweep: Sweep,
              build_fn: Callable[[float, int], Topology],
              engine: str | ThroughputEngine = "exact", *,
              meta_reduce: Mapping[str, Callable[[Sequence[float]], float]]
              | None = None) -> list[SweepPoint]:
    """Run one declarative sweep (``run_sweeps`` with a single item): every
    (x, run) instance goes through ONE ``solve_batch`` call; an empty
    ``sweep.xs`` returns ``[]``."""
    return run_sweeps([(sweep, build_fn)], engine,
                      meta_reduce=meta_reduce)[0]

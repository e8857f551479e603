"""BatchPlan — the planning/execution core for batched dual solves.

Every figure in the paper is thousands of independent max-concurrent-flow
instances (20 runs per point, many points per figure, Figs. 3-7 are whole
grids).  This module turns one heterogeneous pile of (topology, demand)
instances into an explicit execution plan and runs it:

1. **Buckets** — instances are grouped by padded node count
   (``bucket_size``: pow2 / mult128 / fixed multiple / exact), and every
   member of a bucket is padded to the bucket's largest member, so an
   equal-size group (the per-figure common case) pads nothing.  Padded
   nodes carry zero capacity/demand and are masked out of the dual ratio
   (see ``repro.core.mcf``).
2. **Chunks** — each bucket's batch axis is split into chunks under a
   configurable lane budget (``max_lanes``), bounding device memory per
   launch and letting early-stopping chunks retire without waiting for the
   slowest lane of the whole bucket.  When a bucket needs several chunks
   they all share one lane count (the trailing chunk is padded with
   replicated lanes), so XLA compiles ONE program per (bucket, chunk-shape)
   — ``PlanStats.compile_keys`` lists exactly those shapes.
3. **Devices** — each chunk's batch axis is sharded across a 1-D
   ``jax.sharding.Mesh`` of ``devices`` local devices via ``NamedSharding``
   (the chunk lane count is always a device-count multiple; surplus lanes
   replicate a real instance and are dropped on unpack, so per-lane results
   are bit-identical to a single-device run).
4. **Async dispatch** — all chunks are dispatched without blocking
   (``solve_*_batch(..., block=False)`` donates the device input buffers
   and returns in-flight arrays); the host syncs ONCE at the end with
   ``jax.block_until_ready`` over the whole set, so devices overlap chunk
   execution instead of round-tripping per bucket.

A plan is solver-agnostic: ``execute(solver="dual")`` (the default) runs
the certified-upper-bound dual descent (``repro.core.mcf``) and
``execute(solver="primal")`` runs the Frank–Wolfe primal solver
(``repro.core.primal``, certified lower bound + the free dual bound) —
primal lanes ride exactly the same buckets/chunks/sharding as dual lanes.

``DualEngine``/``PrimalEngine``/``CertifiedEngine``/``AutoEngine``
(``repro.core.engine``) delegate their ``solve_batch`` here; ``run_sweeps``
routes entire figure families through one ``BatchPlan``; the fleet
optimizer (``repro.design``) re-executes the SAME plan structure every
search round via ``refill`` — new candidate wirings, identical
buckets/chunks/compile keys, so a whole multi-round search compiles each
solver once.  This seam is where multi-host dispatch, streaming sweeps,
and result caching plug in.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import numpy as np

from repro.core import aotcache, mcf, primal, routing, spans
from repro.core.graphs import Topology, as_cap, degree_stats

__all__ = ["bucket_size", "device_count", "compile_cache_sizes", "Chunk",
           "PlanStats", "InstanceSolve", "SOLVERS", "BatchPlan"]


def bucket_size(n: int, mode: str | int | None) -> int:
    """Padded size for an ``n``-node instance under a bucketing ``mode``:
    ``"pow2"`` (next power of two, floor 8), ``"mult128"`` (next multiple
    of 128 — TPU tile-aligned), an ``int`` m (next multiple of m), or
    ``None``/``"none"``/``"exact"`` (no padding: group by exact size)."""
    if mode in (None, "none", "exact"):
        return n
    if mode == "pow2":
        return max(8, 1 << (n - 1).bit_length())
    if mode == "mult128":
        mode = 128
    if isinstance(mode, int) and mode > 0:
        return -(-n // mode) * mode
    raise ValueError(f"unknown bucket mode {mode!r}; expected 'pow2', "
                     "'mult128', a positive int, or None")


def device_count(devices: int | None = None) -> int:
    """Resolve a ``devices`` knob: ``None`` means every local device."""
    import jax
    avail = len(jax.local_devices())
    if devices is None:
        return avail
    if not 1 <= devices <= avail:
        raise ValueError(f"devices={devices} out of range; "
                         f"{avail} local device(s) available")
    return int(devices)


@dataclasses.dataclass(frozen=True)
class Chunk:
    """One device launch: a slice of a bucket, padded to ``lanes`` rows."""

    bucket: int                # bucket key the members were grouped under
    padded_n: int              # node-dim target (largest member in bucket)
    indices: tuple[int, ...]   # original instance positions (real lanes)
    lanes: int                 # batch rows incl. padding (devices multiple)

    @property
    def pad_lanes(self) -> int:
        return self.lanes - len(self.indices)


@dataclasses.dataclass(frozen=True)
class PlanStats:
    """What the planner decided — reported in result ``meta`` and benches."""

    instances: int
    buckets: int
    chunks: int
    devices: int
    max_lanes: int | None
    lanes_total: int           # sum of chunk lane counts (incl. padding)
    lanes_padded: int          # replicated lanes added for shape/device fit
    compile_keys: tuple[tuple[int, int], ...]   # distinct (padded_n, lanes)

    def as_dict(self) -> dict[str, Any]:
        # compile_keys stays a tuple of tuples: immutable, still JSON-able
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class InstanceSolve:
    """Per-instance solver output of an executed plan (solver-agnostic).

    ``value`` is the solver's headline certified bound on the instance's
    θ* (per-unit-demand max concurrent flow rate): a certified UPPER
    bound under ``solver="dual"``, a certified LOWER bound under
    ``solver="primal"`` (whose free dual upper bound lands in
    ``meta["ub"]`` — the pair is a provable bracket).  Everything else
    the solver reports (dual: ``final_ratio``; primal: ``ub`` and
    ``final_util``) lands in ``meta`` alongside the plan placement.
    """

    value: float
    iterations: int
    meta: Mapping[str, Any]


def _dispatch_dual(capp, demp, n_valid, sharding, solver_kw):
    r = mcf.solve_dual_batch(capp, demp, n_valid=n_valid, sharding=sharding,
                             donate=True, block=False, **solver_kw)
    return {"value": r.throughput_ub, "final_ratio": r.final_ratio,
            "iterations": r.iterations}


def _dispatch_primal(capp, demp, n_valid, sharding, solver_kw):
    r = primal.solve_primal_batch(capp, demp, n_valid=n_valid,
                                  sharding=sharding, donate=True,
                                  block=False, **solver_kw)
    return {"value": r.throughput_lb, "ub": r.throughput_ub,
            "final_util": r.final_util, "iterations": r.iterations}


def _dispatch_dual_demgrad(capp, demp, n_valid, sharding, solver_kw):
    r = mcf.solve_dual_demgrad_batch(capp, demp, n_valid=n_valid,
                                     sharding=sharding, donate=True,
                                     block=False, **solver_kw)
    return {"value": r.throughput_ub, "final_ratio": r.final_ratio,
            "iterations": r.iterations, "dem_grad": r.dem_grad}


def _dispatch_ecmp(capp, demp, n_valid, sharding, solver_kw):
    r = routing.solve_ecmp_batch(capp, demp, n_valid=n_valid,
                                 sharding=sharding, donate=True,
                                 block=False, **solver_kw)
    return {"value": r.throughput_lb, "ub": r.throughput_ub,
            "final_util": r.final_util, "iterations": r.iterations}


def _dispatch_ksp(capp, demp, n_valid, sharding, solver_kw):
    r = routing.solve_ksp_batch(capp, demp, n_valid=n_valid,
                                sharding=sharding, donate=True,
                                block=False, **solver_kw)
    return {"value": r.throughput_lb, "ub": r.throughput_ub,
            "final_util": r.final_util, "iterations": r.iterations}


# chunk dispatchers by solver name: (capp, demp, n_valid, sharding,
# solver_kw) -> dict of in-flight per-lane arrays; "value" is the headline
# bound, every other key is copied into the per-instance meta
SOLVERS = {"dual": _dispatch_dual, "primal": _dispatch_primal,
           "dual-demgrad": _dispatch_dual_demgrad,
           "ecmp": _dispatch_ecmp, "ksp": _dispatch_ksp}


def compile_cache_sizes() -> dict[str, int | None]:
    """Compiled-program counts per (solver backend, entry point) — e.g.
    ``{"dual.solve_batch": 3, "primal.solve_batch": 1, ...}``.  Benchmarks
    report deltas of this to show "one compile per (bucket, chunk-shape)";
    ``None`` = the installed jax lacks cache introspection.  Also carries
    the persistent AOT cache counters (``aot.compiles`` / ``aot.hits``,
    always-present ints — zero when the cache is off) so warm-run checks
    can assert "no new XLA compiles" across processes."""
    out: dict[str, int | None] = {}
    for name, mod in (("dual", mcf), ("primal", primal),
                      ("routing", routing)):
        for k, v in mod.compile_cache_sizes().items():
            out[f"{name}.{k}"] = v
    a = aotcache.stats()
    out["aot.compiles"] = a["compiles"]
    out["aot.hits"] = a["hits"]
    return out


class BatchPlan:
    """An executable plan over one pile of (topology, demand) instances."""

    def __init__(self, caps: list[np.ndarray], dems: list[np.ndarray],
                 chunks: list[Chunk], devices: int,
                 max_lanes: int | None, bucket_mode: str | int | None):
        self.caps = caps
        self.dems = dems
        self.chunks = chunks
        self.devices = devices
        self.max_lanes = max_lanes
        self.bucket_mode = bucket_mode
        self.stats = PlanStats(
            instances=len(caps), buckets=len({c.bucket for c in chunks}),
            chunks=len(chunks), devices=devices, max_lanes=max_lanes,
            lanes_total=sum(c.lanes for c in chunks),
            lanes_padded=sum(c.pad_lanes for c in chunks),
            compile_keys=tuple(sorted({(c.padded_n, c.lanes)
                                       for c in chunks})))

    @classmethod
    @spans.span("plan.build")
    def build(cls, topos: Sequence[Topology | np.ndarray],
              dems: Sequence[np.ndarray], *,
              bucket: str | int | None = "pow2",
              max_lanes: int | None = None,
              devices: int | None = None) -> "BatchPlan":
        """Plan ``len(topos)`` instances: bucket by padded size, chunk each
        bucket under ``max_lanes`` rows per launch, pad each chunk's batch
        axis to a multiple of ``devices``.  Every launch spans all devices,
        so one lane per device is the floor: a ``max_lanes`` below the
        device count (or not a multiple of it) is rounded to the nearest
        feasible budget, never silently exceeded beyond that floor."""
        if len(topos) != len(dems):
            raise ValueError(f"topos ({len(topos)}) and dems ({len(dems)}) "
                             "must have equal length")
        if max_lanes is not None and max_lanes < 1:
            raise ValueError(f"max_lanes must be >= 1, got {max_lanes}")
        caps = [np.asarray(as_cap(t), np.float32) for t in topos]
        demsl = [np.asarray(d, np.float32) for d in dems]
        ndev = device_count(devices)
        by_bucket: dict[int, list[int]] = {}
        for i, c in enumerate(caps):
            by_bucket.setdefault(bucket_size(c.shape[0], bucket),
                                 []).append(i)
        chunks: list[Chunk] = []
        for bkt, idx in sorted(by_bucket.items()):
            # pad to the largest member, not the bucket ceiling: same one
            # compile per (bucket, chunk-shape), but an equal-size group
            # pads no nodes at all
            size = max(caps[i].shape[0] for i in idx)
            need = -(-len(idx) // ndev) * ndev   # device multiple that fits
            if max_lanes is None:
                lanes = need
            else:
                # floor the budget to a device multiple (never below one
                # lane per device), and never pad a small bucket up to it
                lanes = min(max(ndev, max_lanes // ndev * ndev), need)
            for lo in range(0, len(idx), lanes):
                chunks.append(Chunk(bucket=bkt, padded_n=size,
                                    indices=tuple(idx[lo:lo + lanes]),
                                    lanes=lanes))
        return cls(caps, demsl, chunks, ndev, max_lanes, bucket)

    def refill(self, topos: Sequence[Topology | np.ndarray],
               dems: Sequence[np.ndarray]) -> "BatchPlan":
        """A new plan over fresh instances that reuses THIS plan's chunk
        structure (same buckets, chunk shapes, device layout — so exactly
        the same XLA compile keys, guaranteed structurally rather than by
        re-planning and hoping).  The new pile must match instance-for-
        instance: same length, and instance ``i`` must have the same node
        count as before (``ValueError`` otherwise — fall back to
        ``build``).  This is the fleet-search fast path: a stochastic
        optimizer proposing same-size candidate wirings every round pays
        the planner cost once and zero recompiles after round one."""
        if len(topos) != len(self.caps):
            raise ValueError(f"refill needs {len(self.caps)} instances "
                             f"(the planned count), got {len(topos)}")
        caps = [np.asarray(as_cap(t), np.float32) for t in topos]
        for i, (old, new) in enumerate(zip(self.caps, caps)):
            if old.shape != new.shape:
                raise ValueError(
                    f"refill instance {i} is {new.shape[0]} nodes, planned "
                    f"for {old.shape[0]}; rebuild the plan for a new size "
                    "profile")
        demsl = [np.asarray(d, np.float32) for d in dems]
        clone = object.__new__(type(self))
        clone.__dict__.update(self.__dict__)
        clone.caps = caps
        clone.dems = demsl
        return clone

    def _sharding(self):
        """NamedSharding of the batch axis over a 1-D device mesh (or None
        on a single-device plan — computation stays on the default device)."""
        if self.devices <= 1:
            return None
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        # an Auto axis: jax.make_mesh defaults to Explicit axes, whose
        # sharding-in-types cannot batch the solvers' lax.cond under vmap
        mesh = jax.make_mesh((self.devices,), ("batch",),
                             axis_types=(jax.sharding.AxisType.Auto,),
                             devices=jax.local_devices()[:self.devices])
        return NamedSharding(mesh, P("batch"))

    def _pack(self, chunk: Chunk):
        """Materialise one chunk's padded [lanes, n, n] arrays.  Surplus
        lanes replicate the chunk's first instance (never a zero instance:
        a 0/0 dual ratio would poison the lane with NaNs) and are dropped
        on unpack."""
        s = chunk.padded_n
        capp = np.zeros((chunk.lanes, s, s), np.float32)
        demp = np.zeros((chunk.lanes, s, s), np.float32)
        n_valid = np.empty(chunk.lanes, np.int32)
        rows = list(chunk.indices) + [chunk.indices[0]] * chunk.pad_lanes
        for lane, i in enumerate(rows):
            n = self.caps[i].shape[0]
            capp[lane, :n, :n] = self.caps[i]
            demp[lane, :n, :n] = self.dems[i]
            n_valid[lane] = n
        return capp, demp, n_valid

    def _density_hints(self, chunk: Chunk) -> dict[str, Any]:
        """Per-chunk sparsity stats from the UNPADDED member instances, so
        the batch solvers' host-side ``resolve_backend_density`` never has
        to scan the padded [lanes, n, n] stack: the ell-bf table width is
        the widest member's max degree, and the density gate uses the
        densest member's mean degree (sparse only when every lane is)."""
        d_max, mean = 0, 0.0
        for i in chunk.indices:
            dm, md = degree_stats(self.caps[i])
            d_max = max(d_max, dm)
            mean = max(mean, md)
        return {"d_max": max(1, d_max), "mean_degree": mean}

    def execute(self, solver: str = "dual",
                **solver_kw) -> list[InstanceSolve]:
        """Dispatch every chunk asynchronously (sharded over the plan's
        devices), sync once, and scatter per-instance results back into
        input order.  ``solver`` picks the batch solver (``SOLVERS``:
        "dual", "primal", "dual-demgrad" — the latter additionally
        returns each lane's demand gradient in ``meta["dem_grad"]`` —
        or the routing-restricted "ecmp" / "ksp" lower-bound programs);
        ``solver_kw`` goes to its ``solve_*_batch``
        (iters/lr/tol/check_every/use_pallas/interpret/backend/d_max/
        max_rounds).  When the backend can land on ``"ell-bf"`` and the
        caller gave no explicit table stats, each chunk gets density hints
        computed from its own unpadded members (``_density_hints``)."""
        import jax
        try:
            dispatch = SOLVERS[solver]
        except KeyError:
            raise ValueError(f"unknown plan solver {solver!r}; "
                             f"known: {sorted(SOLVERS)}") from None
        sharding = self._sharding()
        want_hints = (solver_kw.get("backend") in (None, "auto", "ell-bf")
                      and not solver_kw.get("use_pallas")
                      and "d_max" not in solver_kw
                      and "mean_degree" not in solver_kw)
        pending = []
        for chunk in self.chunks:
            with spans.span("plan.pack"):
                capp, demp, n_valid = self._pack(chunk)
                kw = ({**solver_kw, **self._density_hints(chunk)}
                      if want_hints else solver_kw)
            with spans.span("plan.dispatch"):
                pending.append(dispatch(capp, demp, n_valid, sharding, kw))
        # ONE host sync for the whole plan: chunks overlap on-device while
        # the host is still packing/dispatching later ones
        with spans.span("plan.sync"):
            jax.block_until_ready([list(r.values()) for r in pending])
        with spans.span("plan.unpack"):
            return self._unpack(pending)

    def _unpack(self, pending: list[dict]) -> list[InstanceSolve]:
        """Scatter the synced per-chunk results back into input order."""
        stats = self.stats.as_dict()   # values immutable; copied per result
        out: list[InstanceSolve | None] = [None] * len(self.caps)
        for ci, (chunk, res) in enumerate(zip(self.chunks, pending)):
            arrs = {k: np.asarray(v) for k, v in res.items()}
            for lane, i in enumerate(chunk.indices):
                # per-lane scalars become floats (iterations: int); non-
                # scalar per-lane outputs (e.g. the dual-demgrad solver's
                # [n, n] demand gradient) stay np arrays, cropped back to
                # the instance's unpadded node count
                n = int(self.caps[i].shape[0])
                solved = {}
                for k, a in arrs.items():
                    if k == "value":
                        continue
                    if k == "iterations":
                        solved[k] = int(a[lane])
                    elif a[lane].ndim == 0:
                        solved[k] = float(a[lane])
                    else:
                        solved[k] = np.asarray(a[lane])[tuple(
                            slice(n) for _ in range(a[lane].ndim))]
                out[i] = InstanceSolve(
                    value=float(arrs["value"][lane]),
                    iterations=int(arrs["iterations"][lane]),
                    meta={**solved,
                          "bucket": chunk.bucket,
                          "padded_n": chunk.padded_n,
                          "nodes": int(self.caps[i].shape[0]),
                          "batch_size": len(chunk.indices),
                          "chunk": ci, "chunks": len(self.chunks),
                          "devices": self.devices, "plan": dict(stats)})
        return out  # type: ignore[return-value]

"""The paper's contribution: high-throughput topology design + flow engines.

Modules: graphs (Topology + generation), traffic (named demand patterns),
engine (unified ThroughputEngine registry + declarative sweeps), plan
(BatchPlan: bucketed/chunked/device-sharded batch execution core), batch
(the batched solvers' one table and runner), lp (exact
HiGHS max-concurrent-flow), mcf (JAX dual solver on min-plus APSP: certified
upper bounds), primal (Frank-Wolfe shortest-path-routing primal solver:
certified lower bounds, fused lb/ub brackets), bounds (Thm 1 / Cerf d* /
Eqn 1-2), decompose (T = C.U/(f.D.AS)), heterogeneous (Figs 3-7 drivers),
vl2 (Fig 11), fabric (topology -> collective bandwidth for the training
runtime).  The design layer on top — fleet search over wirings through
one BatchPlan per round — lives in ``repro.design``.

The public entry points are re-exported here::

    from repro.core import Topology, get_engine, run_sweep, Sweep, traffic

    topo = graphs.random_regular_graph(40, 10, seed=0, servers=5)
    dem = traffic.make("permutation", topo.servers, seed=1)
    result = get_engine("exact").solve(topo, dem)   # ThroughputResult
"""
from repro.core import (  # noqa: F401
    bounds, decompose, engine, fabric, graphs, heterogeneous, lp, mcf,
    plan, primal, traffic, vl2,
)
from repro.core.engine import (  # noqa: F401
    CertifiedEngine, DualEngine, ExactLPEngine, PrimalEngine, Sweep,
    SweepPoint, ThroughputEngine, ThroughputResult, as_engine, get_engine,
    run_sweep, run_sweeps,
)
from repro.core.graphs import Topology  # noqa: F401
from repro.core.plan import BatchPlan, PlanStats  # noqa: F401

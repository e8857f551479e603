"""VL2 rewiring (paper §7) + fabric collective-bandwidth model."""
import numpy as np
import pytest

from repro.core import fabric, lp, traffic, vl2


SPEC = vl2.VL2Spec(d_a=6, d_i=6, servers_per_tor=5)


def test_vl2_structure():
    topo = vl2.vl2_topology(SPEC)
    topo.validate()
    n_tor, na, nc = SPEC.n_tor_full, SPEC.n_agg, SPEC.n_core
    assert topo.n == n_tor + na + nc
    # ToRs: exactly 2 x 10G uplinks
    assert np.all(topo.cap[:n_tor].sum(1) == 2 * vl2.FABRIC)
    # full bipartite agg-core
    agg_core = topo.cap[n_tor:n_tor + na, n_tor + na:]
    assert np.all(agg_core == vl2.FABRIC)


def test_vl2_single_agg_doubles_uplink():
    # na == 1 (d_i = 1): round-robin has nowhere else to go, so BOTH ToR
    # uplinks land on the single agg as one doubled-capacity link (pins the
    # intended behaviour after removing the dead a2-reassignment branch)
    spec = vl2.VL2Spec(d_a=4, d_i=1, servers_per_tor=5)
    assert spec.n_agg == 1
    topo = vl2.vl2_topology(spec)
    n_tor, agg0 = spec.n_tor_full, spec.n_tor_full
    assert np.all(topo.cap[:n_tor, agg0] == 2 * vl2.FABRIC)
    assert np.all(topo.cap[:n_tor].sum(1) == 2 * vl2.FABRIC)
    topo.validate()


def test_vl2_supports_full_throughput_by_design():
    topo = vl2.vl2_topology(SPEC)
    dem = traffic.random_permutation(topo.servers, 0)
    th = lp.max_concurrent_flow(topo.cap, dem, want_flows=False).throughput
    assert th >= 1.0 - 1e-6


def test_rewired_vl2_uses_same_equipment():
    topo = vl2.rewired_vl2_topology(SPEC, SPEC.n_tor_full, seed=0)
    topo.validate()
    n_tor = SPEC.n_tor_full
    # same ToR uplink count and same total fabric port count (+- parity fixup)
    assert np.all(topo.cap[:n_tor].sum(1) == 2 * vl2.FABRIC)
    ports_used = topo.cap.sum() / vl2.FABRIC   # stub count (both dirs)
    max_ports = 2 * n_tor * 2 + 0  # uplinks counted twice
    total_fabric_ports = SPEC.n_agg * SPEC.d_a + SPEC.n_core * SPEC.d_i
    assert ports_used <= (2 * n_tor + total_fabric_ports) + 1


@pytest.mark.parametrize("spec,n_tor", [(vl2.VL2Spec(6, 6, 20), 12),
                                         (vl2.VL2Spec(22, 22, 20), 173)])
def test_rewired_vl2_keeps_every_switch_within_its_ports(spec, n_tor):
    """Each ToR keeps its two uplinks and each other switch its quota of
    them, so no switch is wired past its port count and every seed uses
    the same ports: one idle port at most (an odd port count)."""
    ports = vl2.FABRIC * np.concatenate([np.full(spec.n_agg, spec.d_a),
                                         np.full(spec.n_core, spec.d_i)])
    used = []
    for seed in range(8):
        topo = vl2.rewired_vl2_topology(spec, n_tor, seed)
        attached = topo.cap.sum(axis=0)
        assert np.all(attached[:n_tor] == 2 * vl2.FABRIC)
        assert np.all(attached[n_tor:] <= ports)
        assert (ports - attached[n_tor:]).sum() <= vl2.FABRIC
        used.append(attached)
    assert all(np.array_equal(u, used[0]) for u in used)


def test_rewired_supports_at_least_as_many_tors():
    # paper ratio: 20 x 1G servers vs 2 x 10G uplinks (exactly balanced)
    spec20 = vl2.VL2Spec(d_a=4, d_i=4, servers_per_tor=20)
    base = spec20.n_tor_full
    best = vl2.max_tors_at_full_throughput(
        spec20, vl2.rewired_vl2_topology, lo=base, hi=base + 4, runs=2,
        seed0=0)
    assert best >= base, "rewiring must not lose capacity (paper Fig. 11)"


def test_binary_search_raises_on_bad_lower():
    def broken(spec, n_tor, seed):
        t = vl2.rewired_vl2_topology(spec, n_tor, seed)
        cap = t.cap * 1e-3    # starved network
        return type(t)(cap=cap, servers=t.servers, labels=t.labels)
    with pytest.raises(ValueError):
        vl2.max_tors_at_full_throughput(SPEC, broken, lo=4, hi=8, runs=1)


# ---------------------------------------------------------------------------
# fabric model
# ---------------------------------------------------------------------------

def test_fabric_design_valid():
    d = fabric.design_fabric([24] * 4 + [8] * 8, num_pods=12, seed=0)
    d.topology.validate()
    assert len(d.pod_switch) == 12
    assert d.topology.servers.sum() == 12


def test_fabric_paper_rule_beats_tor_packing():
    cmp = fabric.compare_with_traditional([24] * 4 + [8] * 8, num_pods=12,
                                          runs=2)
    assert cmp["paper"] > cmp["traditional"]


def test_collective_patterns():
    d = fabric.design_fabric([16] * 6, num_pods=8, seed=1)
    ring = fabric.collective_bandwidth(d, "ring")
    a2a = fabric.collective_bandwidth(d, "alltoall")
    ag = fabric.collective_bandwidth(d, "allgather")
    assert ring > 0 and a2a > 0 and ag > 0
    assert ag <= a2a + 1e-6, "allgather moves (P-1)x the volume"

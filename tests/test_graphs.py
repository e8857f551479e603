"""Topology generation invariants (core.graphs)."""
import numpy as np
import pytest
from tests._hypothesis import given, st

from repro.core import graphs, spans
from repro.core import heterogeneous as het


@given(st.integers(6, 30), st.integers(2, 5), st.integers(0, 10_000))
def test_rrg_is_simple_and_regular(n, r, seed):
    if n * r % 2 != 0:
        n += 1
    if r >= n:
        return
    topo = graphs.random_regular_graph(n, r, seed)
    topo.validate()
    cap = topo.cap
    assert np.allclose(cap, cap.T)
    assert np.all(np.diag(cap) == 0)
    assert np.all(cap <= 1.0), "simple graph: no multi-edges"
    assert np.all((cap > 0).sum(axis=1) == r)


@given(st.lists(st.integers(1, 6), min_size=6, max_size=20),
       st.integers(0, 10_000))
def test_degree_sequence_respected(degs, seed):
    degs = np.asarray(degs)
    if degs.sum() % 2 != 0:
        degs[0] += 1
    if degs.max() >= len(degs):
        return
    cap = graphs.random_graph_from_degrees(degs, seed).cap
    # capacity-weighted degree holds even if the repair fell back to
    # parallel links for a near-non-graphical sequence
    assert np.all(cap.sum(axis=1) == degs)


def test_multigraph_mode_preserves_degrees():
    degs = [20, 20, 3, 3, 3, 3]   # not graphical as a simple graph
    cap = graphs.random_graph_from_degrees(degs, 0, allow_multi=True).cap
    assert np.all(cap.sum(axis=1) == degs)
    assert np.all(np.diag(cap) == 0)


@pytest.mark.parametrize("bias", [0.2, 1.0, 1.8])
def test_two_cluster_cross_edges_track_bias(bias):
    deg_a = [10] * 12
    deg_b = [6] * 16
    topo = graphs.biased_two_cluster_graph(deg_a, deg_b, bias, seed=1)
    cap, labels = topo.cap, topo.labels
    a = labels == 0
    cross = cap[a][:, ~a].sum()
    sa, sb = 120.0, 96.0
    expected = bias * sa * sb / (sa + sb - 1)
    assert cross == pytest.approx(expected, rel=0.15, abs=4)
    assert np.all((cap > 0).sum(1) == np.concatenate([deg_a, deg_b]))


def test_two_cluster_mismatched_stub_parity_raises():
    # sum(deg_a)=7 odd, sum(deg_b)=8 even: no cross-edge count can leave
    # both clusters with an even leftover stub count.  Used to spin forever
    # in the parity fixup loop; must fail fast instead.
    with pytest.raises(ValueError, match="parity"):
        graphs.biased_two_cluster_graph([3, 2, 2], [2, 2, 2, 2], 1.0, seed=0)


def test_two_cluster_same_parity_still_builds():
    topo = graphs.biased_two_cluster_graph([3, 3, 2], [2, 2, 2, 2], 1.0,
                                           seed=0)
    topo.validate()
    assert topo.cap.sum() == 8 + 8  # all 16 stubs paired


def test_distribute_servers_proportional_and_capped():
    ports = [30, 30, 10, 10, 10]
    srv = graphs.distribute_servers(ports, 45, beta=1.0)
    assert srv.sum() == 45
    assert srv[0] == srv[1] and srv[2] == srv[3] == srv[4]
    assert srv[0] / srv[2] == pytest.approx(3.0, rel=0.25)
    srv2 = graphs.distribute_servers([5, 5, 5], 12)
    assert srv2.sum() == 12 and np.all(srv2 <= 4)


def test_power_law_degrees_in_range():
    ks = graphs.power_law_degrees(200, 4, 48, alpha=2.0, seed=0)
    assert ks.min() >= 4 and ks.max() <= 48
    assert (ks <= 12).mean() > 0.5, "power law should skew small"


def test_power_law_degrees_degenerate_and_invalid_ranges():
    # k_min == k_max: constant draw, not a crash (expansion steps start
    # from single-class pools)
    ks = graphs.power_law_degrees(50, 6, 6, alpha=2.0, seed=0)
    assert np.all(ks == 6)
    with pytest.raises(ValueError, match="k_min"):
        graphs.power_law_degrees(10, 0, 4, alpha=2.0, seed=0)
    with pytest.raises(ValueError, match="empty degree range"):
        graphs.power_law_degrees(10, 5, 4, alpha=2.0, seed=0)


def test_distribute_servers_edge_cases():
    # zero servers: all-zero vector, same length as the pool
    z = graphs.distribute_servers([8, 8, 8], 0)
    assert z.shape == (3,) and z.sum() == 0
    # fewer servers than switches: nothing lost, nothing negative
    few = graphs.distribute_servers([8, 8, 8, 8, 8], 2)
    assert few.sum() == 2 and np.all(few >= 0)
    # empty pool: fine for zero servers, loud otherwise
    assert graphs.distribute_servers([], 0).shape == (0,)
    with pytest.raises(ValueError, match="empty switch pool"):
        graphs.distribute_servers([], 3)
    with pytest.raises(ValueError, match="num_servers"):
        graphs.distribute_servers([8, 8], -1)


def test_connected_components_labels():
    topo = graphs.random_regular_graph(12, 3, seed=0)
    assert len(np.unique(graphs.connected_components(topo))) == 1
    cut = topo.degrade(dead_switches=[0])
    labels = graphs.connected_components(cut)
    assert labels[0] != labels[1], "a dead switch is its own component"


# ---------------------------------------------------------------------------
# two-cluster repair against its plain scalar loop
# ---------------------------------------------------------------------------

def _reference_repair_two_cluster(adj, na, rng, max_iter=20_000):
    """The two-cluster repair as one scalar loop: partners listed in
    Python, shuffled as a list and tried one at a time.  Returns the
    repaired matrix and the counts the ``graphs.repair`` span records."""
    adj = adj.copy()
    counts = {"iterations": 0, "stalled": 0}

    def is_cross(u, v):
        return (u < na) != (v < na)

    best_bad = np.inf
    stall = 0
    for it in range(max_iter):
        bad_self = np.flatnonzero(np.diag(adj) > 0)
        multi = np.argwhere(np.triu(adj, 1) > 1)
        if len(bad_self) == 0 and len(multi) == 0:
            counts["iterations"] = it
            return adj, counts
        bad = len(bad_self) + len(multi)
        if bad < best_bad:
            best_bad, stall = bad, 0
        else:
            stall += 1
            if stall > 200:
                counts.update(iterations=it, stalled=1)
                break
        if len(bad_self) > 0:
            i = int(rng.integers(len(bad_self)))
            u = v = int(bad_self[i])
        else:
            i = int(rng.integers(len(multi)))
            u, v = int(multi[i][0]), int(multi[i][1])
        cross = is_cross(u, v)
        xs, ys = np.nonzero(np.triu(adj, 1) if cross else adj)
        same = [(int(x), int(y)) for x, y in zip(xs, ys)
                if is_cross(x, y) == cross
                and (cross or (x < na) == (u < na))]
        rng.shuffle(same)
        for x, y in same[:600]:
            if cross:
                a1, b1 = (u, v) if u < na else (v, u)
                a2, b2 = (x, y) if x < na else (y, x)
                if a1 == a2 or b1 == b2:
                    continue
                if adj[a1, b2] > 0 or adj[a2, b1] > 0:
                    continue
                new_edges = ((a1, b2), (a2, b1))
                old_edges = ((a1, b1), (a2, b2))
            else:
                if len({u, v, x, y}) < (3 if u == v else 4):
                    continue
                if u == x or v == y or adj[u, x] > 0 or adj[v, y] > 0:
                    continue
                if u == v and (adj[u, y] > 0 or x == y):
                    continue
                if u == v:
                    new_edges = ((u, x), (u, y))
                else:
                    new_edges = ((u, x), (v, y))
                old_edges = ((u, v), (x, y))
            for (p, q) in old_edges:
                adj[p, q] -= 1
                if p != q:
                    adj[q, p] -= 1
                else:
                    adj[p, q] -= 1
            for (p, q) in new_edges:
                adj[p, q] += 1
                adj[q, p] += 1
            break
    else:
        counts["iterations"] = max_iter
    for u in np.flatnonzero(np.diag(adj) > 0):
        adj[u, u] = 0
    return adj, counts


def _generator_at(state):
    rng = np.random.default_rng(0)
    rng.bit_generator.state = state
    return rng


def _repair_and_reference(adj, na, rng, max_iter=20_000, repair=None):
    """Run the repair on ``rng`` and the reference on a generator in the
    same state; return both matrices, both states after, and both sets of
    span counts."""
    ref_rng = _generator_at(rng.bit_generator.state)
    repair = repair or graphs._repair_two_cluster
    got = repair(adj, na, rng, max_iter=max_iter)
    rec = spans.records()[-1]
    assert rec.name == "graphs.repair"
    want, counts = _reference_repair_two_cluster(adj, na, ref_rng, max_iter)
    return ((got, rng.bit_generator.state, rec.counts),
            (want, ref_rng.bit_generator.state, counts))


def _assert_same(got, want):
    assert np.array_equal(got[0], want[0]), "repaired fabric differs"
    assert got[1] == want[1], "generator state differs"
    assert got[2] == want[2], "span counts differ"


def _stub_pairing(deg_a, deg_b, n_cross, seed):
    """A two-cluster multigraph straight from random stub pairing, with
    the self-loops and parallel edges the repair has to remove."""
    rng = np.random.default_rng(seed)
    na = len(deg_a)
    sa = rng.permutation(np.repeat(np.arange(na), deg_a))
    sb = rng.permutation(np.repeat(np.arange(len(deg_b)), deg_b) + na)
    pairs = np.concatenate([np.stack([sa[:n_cross], sb[:n_cross]], 1),
                            sa[n_cross:].reshape(-1, 2),
                            sb[n_cross:].reshape(-1, 2)])
    adj = np.zeros((na + len(deg_b),) * 2, np.int64)
    np.add.at(adj, (pairs[:, 0], pairs[:, 1]), 1)
    np.add.at(adj, (pairs[:, 1], pairs[:, 0]), 1)
    return adj, na


@pytest.mark.parametrize("split", [(5, 2), (7, 1), (3, 3)])
@pytest.mark.parametrize("bias", [0.3, 0.7, 1.0, 1.5])
def test_two_cluster_repair_matches_scalar_reference_on_fig6_pool(
        monkeypatch, split, bias):
    """Every repair inside ``build_two_class`` on the Fig. 6 pool (10
    switches of 18 ports, 20 of 6, 90 servers) gives the reference's
    fabric, generator state and span counts."""
    spec = het.TwoClassSpec(n_large=10, k_large=18, n_small=20, k_small=6,
                            num_servers=90)
    repair = graphs._repair_two_cluster
    pairs = []

    def checked(adj, na, rng):
        got, want = _repair_and_reference(adj, na, rng, repair=repair)
        pairs.append((got, want))
        return got[0]

    monkeypatch.setattr(graphs, "_repair_two_cluster", checked)
    # seed 0, and seeds of the benchmark's three pool groups (a group's
    # seed0, plus 1000 per run)
    for seed in (0, 31033112, 274688241, 596577003):
        het.build_two_class(spec, split[0] * spec.n_large, bias, seed)
    assert len(pairs) == 4
    for got, want in pairs:
        _assert_same(got, want)


def _offenders(adj, na):
    """(self-loops, intra multi-edges, cross multi-edges) of a matrix."""
    in_a = np.arange(len(adj)) < na
    multi = np.triu(adj, 1) > 1
    cross = in_a[:, None] != in_a[None, :]
    return (int((np.diag(adj) > 0).sum()), int((multi & ~cross).sum()),
            int((multi & cross).sum()))


def _one_loop_behind_a_wall(na=45):
    """Cluster A: switch 0 has a self-loop and links to 1..42; 1..44 form a
    circulant of degree 16.  Of the 789 same-cluster partners only (43,44)
    and (44,43) can take the loop, so where the shuffle puts them decides
    whether they fall inside the first 600 partners.  Cluster B is one
    link."""
    adj = np.zeros((na + 2, na + 2), np.int64)
    adj[0, 0] = 2
    adj[0, 1:43] = adj[1:43, 0] = 1
    for i in range(44):
        for d in range(1, 9):
            j = (i + d) % 44
            adj[1 + i, 1 + j] = adj[1 + j, 1 + i] = 1
    adj[na, na + 1] = adj[na + 1, na] = 1
    return adj, na


@pytest.mark.parametrize("case", ["self_loops", "cross_multi", "stalled",
                                  "budget", "partner_600th",
                                  "partner_601st"])
def test_two_cluster_repair_matches_scalar_reference_on_offenders(case):
    """Inputs built to reach each path of the repair: intra self-loops,
    cross multi-edges only, a cluster too dense to be simple (the stall
    break), an iteration budget that runs out, and the first valid partner
    shuffled to the 600th place (taken) or the 601st (past the cap, so the
    walk goes on)."""
    max_iter, seed = 20_000, 11
    if case == "self_loops":
        adj, na = _stub_pairing([4] * 8, [3] * 10, 6, seed=3)
        loops, _, _ = _offenders(adj, na)
        assert loops > 0
    elif case == "cross_multi":
        adj, na = _stub_pairing([3] * 5, [3] * 5, 15, seed=1)
        loops, intra, cross = _offenders(adj, na)
        assert loops == intra == 0 and cross > 0
    elif case == "stalled":
        adj, na = _stub_pairing([6] * 4, [2] * 6, 0, seed=2)
    elif case == "budget":
        adj, na = _stub_pairing([8] * 12, [8] * 12, 40, seed=4)
        max_iter = 3
    else:
        # seeds whose first shuffle puts the first valid partner at index
        # 599 (the 600th) or 600 (the 601st)
        adj, na = _one_loop_behind_a_wall()
        seed = 4382 if case == "partner_600th" else 491
    got, want = _repair_and_reference(adj, na, np.random.default_rng(seed),
                                      max_iter=max_iter)
    _assert_same(got, want)
    if case == "stalled":
        assert want[2]["stalled"] == 1
    elif case == "budget":
        assert want[2] == {"iterations": 3, "stalled": 0}
    else:
        assert want[2]["stalled"] == 0
        assert _offenders(want[0], na) == (0, 0, 0)
    if case == "partner_600th":
        assert want[2]["iterations"] == 1
    elif case == "partner_601st":
        assert want[2]["iterations"] > 1


@pytest.mark.parametrize("length", [0, 1, 2, 3, 317, 600, 1200])
def test_generator_shuffle_draws_alike_for_list_and_index_array(length):
    """The two-cluster repair shuffles partner indices where it once
    shuffled the partner list; its fabrics stay the same only while
    ``Generator.shuffle`` gives both the same permutation and draws."""
    listed = np.random.default_rng(length)
    indexed = np.random.default_rng(length)
    partners = [(i, -i) for i in range(length)]
    listed.shuffle(partners)
    order = np.arange(length)
    indexed.shuffle(order)
    assert [p[0] for p in partners] == order.tolist()
    assert listed.bit_generator.state == indexed.bit_generator.state

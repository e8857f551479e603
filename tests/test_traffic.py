"""Traffic matrix invariants (core.traffic).

Property tests (hypothesis, skipped cleanly when it is not installed)
cover the structural invariants of every pattern; the plain tests pin the
same invariants on fixed instances so they always run, plus the
``random_permutation`` tiny-instance regression (the old 100-pass fixup
loop silently returned a non-derangement for < 2 servers).
"""
import numpy as np
import pytest
from tests._hypothesis import given, settings, st

from repro.core import traffic


# ---------------------------------------------------------------------------
# random_permutation
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(0, 8), min_size=2, max_size=12)
       .filter(lambda sv: sum(sv) >= 2),
       st.integers(0, 999))
def test_random_permutation_row_col_sums(servers, seed):
    """Every server sends one flow and receives one flow; a same-switch
    pair drops one from BOTH the switch's row and its column sum, so
    row sums == column sums elementwise and both are <= servers."""
    servers = np.asarray(servers)
    dem = traffic.random_permutation(servers, seed)
    sent = dem.sum(axis=1)
    recv = dem.sum(axis=0)
    assert np.all(np.diag(dem) == 0)
    assert np.all(dem >= 0)
    np.testing.assert_array_equal(sent, recv)
    assert np.all(sent <= servers)
    # total flows: all s servers send, minus the dropped same-switch pairs
    assert dem.sum() <= servers.sum()
    assert dem.sum() == traffic.num_flows(dem)


@given(st.integers(2, 40), st.integers(0, 99))
def test_random_permutation_single_switch_per_server_is_derangement(s, seed):
    """One server per switch: the permutation must be a full derangement —
    every switch sends exactly one flow and receives exactly one."""
    servers = np.ones(s, np.int64)
    dem = traffic.random_permutation(servers, seed)
    assert np.all(dem.sum(axis=1) == 1)
    assert np.all(dem.sum(axis=0) == 1)
    assert np.all(np.diag(dem) == 0)


def test_random_permutation_conservation_fixed():
    servers = np.asarray([3, 1, 4, 2, 5])
    dem = traffic.random_permutation(servers, 11)
    np.testing.assert_array_equal(dem.sum(axis=1), dem.sum(axis=0))
    assert np.all(dem.sum(axis=1) <= servers)


def test_random_permutation_is_server_level_derangement():
    servers = np.full(10, 4)
    dem = traffic.random_permutation(servers, 3)
    # totals: 40 servers each send 1 flow; same-switch flows dropped
    assert 30 <= dem.sum() <= 40


@pytest.mark.parametrize("servers", [[0], [1], [0, 0], [1, 0], [0, 1, 0]])
def test_random_permutation_under_two_servers_raises(servers):
    # regression: used to silently fall out of the fixup loop and return
    # an all-zero (or self-loop-only) demand matrix
    with pytest.raises(ValueError, match=">= 2 servers"):
        traffic.random_permutation(np.asarray(servers), seed=0)


def test_random_permutation_two_servers_deterministic():
    # the only derangement of two servers is the swap; on one switch the
    # flows are intra-switch and dropped, on two switches both survive
    dem = traffic.random_permutation(np.array([1, 1]), seed=5)
    assert dem[0, 1] == 1 and dem[1, 0] == 1 and dem.sum() == 2
    dem = traffic.random_permutation(np.array([2]), seed=5)
    assert dem.shape == (1, 1) and dem.sum() == 0


# ---------------------------------------------------------------------------
# all_to_all / all_to_one
# ---------------------------------------------------------------------------

def test_all_to_all():
    dem = traffic.all_to_all(np.array([2, 3, 1]))
    assert dem[0, 1] == 6 and dem[1, 0] == 6 and dem[2, 0] == 2
    assert np.all(np.diag(dem) == 0)


@given(st.lists(st.integers(0, 9), min_size=2, max_size=10))
def test_all_to_all_num_flows(servers):
    servers = np.asarray(servers)
    dem = traffic.all_to_all(servers)
    s = servers.sum()
    # every ordered cross-switch server pair carries one flow
    assert traffic.num_flows(dem) == s * s - (servers * servers).sum()
    assert np.all(np.diag(dem) == 0)


def test_all_to_one_targets_single_switch():
    dem = traffic.all_to_one(np.full(8, 3), seed=1)
    recv = dem.sum(axis=0)
    assert (recv > 0).sum() == 1


def test_all_to_one_zero_servers_raises():
    # regression: servers.sum() == 0 used to divide by zero in the
    # target-draw probabilities instead of failing with a clear message
    with pytest.raises(ValueError, match=">= 1 server"):
        traffic.all_to_one(np.zeros(4, np.int64), seed=0)


def test_all_to_one_single_occupied_switch_raises():
    # all servers on one switch: every flow would be intra-switch and the
    # demand matrix all-zero — reject early instead
    with pytest.raises(ValueError, match=">= 2 switches"):
        traffic.all_to_one(np.array([0, 7, 0]), seed=0)


def test_all_to_one_never_targets_empty_switch():
    # regression: a zero-server switch could previously never be drawn by
    # probability, but the draw ran over ALL switches; the target is now
    # drawn among occupied switches only — pin it across seeds
    servers = np.array([3, 0, 2, 0, 5])
    for seed in range(25):
        dem = traffic.all_to_one(servers, seed)
        target = int(np.flatnonzero(dem.sum(axis=0))[0])
        assert servers[target] > 0
        assert traffic.num_flows(dem) == servers.sum() - servers[target]


@given(st.lists(st.integers(1, 6), min_size=2, max_size=10),
       st.integers(0, 99))
def test_all_to_one_volume(servers, seed):
    servers = np.asarray(servers)
    dem = traffic.all_to_one(servers, seed)
    target = int(np.flatnonzero(dem.sum(axis=0))[0])
    # every other switch sends all its servers; the target sends nothing
    np.testing.assert_array_equal(
        np.delete(dem[:, target], target), np.delete(servers, target))
    assert dem[target, target] == 0
    assert traffic.num_flows(dem) == servers.sum() - servers[target]


# ---------------------------------------------------------------------------
# stride
# ---------------------------------------------------------------------------

@given(st.floats(0.0, 1.0), st.integers(0, 99))
def test_stride_conserves_total_volume(frac, seed):
    servers = np.full(12, 5)
    dem = traffic.stride(servers, frac, seed)
    assert dem.sum() <= servers.sum()
    assert np.all(dem >= 0) and np.all(np.diag(dem) == 0)


@given(st.integers(3, 12), st.integers(1, 6), st.integers(0, 99))
def test_stride_full_flow_conservation(n, per_switch, seed):
    """frac=1: a ToR-level permutation — each switch sends ALL its servers
    to exactly one other switch, and receives its predecessor's."""
    servers = np.full(n, per_switch)
    dem = traffic.stride(servers, 1.0, seed)
    np.testing.assert_array_equal(dem.sum(axis=1), servers)
    np.testing.assert_array_equal(dem.sum(axis=0), servers)
    assert np.all((dem > 0).sum(axis=1) == 1)
    assert np.all(np.diag(dem) == 0)


def test_stride_full_is_tor_level():
    servers = np.full(10, 6)
    dem = traffic.stride(servers, 1.0, 0)
    rows = dem.sum(axis=1)
    assert np.all(rows == 6), "each ToR sends all its servers to one ToR"
    assert np.all((dem > 0).sum(axis=1) == 1)


def test_stride_zero_frac_is_pure_permutation():
    servers = np.full(8, 3)
    dem = traffic.stride(servers, 0.0, seed=4)
    np.testing.assert_array_equal(dem.sum(axis=1), dem.sum(axis=0))
    assert np.all(dem.sum(axis=1) <= servers)


@pytest.mark.parametrize("frac", [-0.1, 1.5, 2.0, -3.0])
def test_stride_frac_out_of_range_raises(frac):
    # regression: frac > 1 used to crash deep inside rng.choice with an
    # opaque "Cannot take a larger sample than population" numpy error
    with pytest.raises(ValueError, match=rf"\[0, 1\].*{frac}"):
        traffic.stride(np.full(6, 2), frac, seed=0)


# ---------------------------------------------------------------------------
# make: seed contract
# ---------------------------------------------------------------------------

def test_make_deterministic_patterns_ignore_seed():
    servers = np.asarray([2, 3, 1, 4])
    a = traffic.make("all_to_all", servers, seed=0)
    b = traffic.make("all_to_all", servers, seed=999)
    np.testing.assert_array_equal(a, b)


def test_make_is_seed_deterministic():
    servers = np.full(8, 3)
    for name, kw in [("permutation", {}), ("all_to_one", {}),
                     ("stride", {"frac": 0.5})]:
        a = traffic.make(name, servers, seed=7, **kw)
        b = traffic.make(name, servers, seed=7, **kw)
        np.testing.assert_array_equal(a, b)


def test_stride_substream_does_not_collide_with_next_seed():
    """Regression for the sub-seed contract: stride used to derive its
    rest-permutation stream as ``seed + 1``, so ``stride(seed=k,
    frac=0)`` reproduced ``permutation(seed=k+1)`` exactly — a caller
    sweeping consecutive seeds sampled correlated traffic.  The
    sub-stream is now keyed as an independent ``(seed, tag)`` stream."""
    servers = np.full(10, 3)
    for seed in range(10):
        sub = traffic.stride(servers, 0.0, seed)   # frac=0: rest = all
        nxt = traffic.random_permutation(servers, seed + 1)
        assert not np.array_equal(sub, nxt), \
            f"stride seed={seed} aliases permutation seed={seed + 1}"


# ---------------------------------------------------------------------------
# registry / num_flows
# ---------------------------------------------------------------------------

# "adversarial" is the one pattern that needs the topology it attacks
# (and a search budget) — it gets its own suite in test_adversarial.py
_SAMPLED = sorted(set(traffic.PATTERNS) - {"adversarial"})


@settings(max_examples=10)
@given(st.sampled_from(_SAMPLED), st.integers(0, 99))
def test_every_pattern_shares_the_core_invariants(name, seed):
    servers = np.asarray([2, 3, 1, 4, 2, 2])
    dem = traffic.make(name, servers, seed)
    assert dem.shape == (6, 6)
    assert np.all(np.diag(dem) == 0), "same-switch flows never hit the net"
    assert np.all(dem >= 0)
    assert 0 < traffic.num_flows(dem) <= servers.sum() ** 2


def test_adversarial_pattern_requires_topology():
    with pytest.raises(ValueError, match="topo"):
        traffic.make("adversarial", np.full(6, 2), seed=0)


@pytest.mark.parametrize("servers", [[5, 5, 5, 5], [20, 20, 0, 0, 0],
                                     [1, 3, 0, 2]])
def test_bench_all_to_all_sends_one_unit_per_server(servers):
    """The benchmark's own pattern (``bench/traffic/all_to_all.py``): each
    server sends one unit in total, split equally over every other
    server; none to itself, and flows inside one switch stay off the
    network."""
    from bench.files import load_module
    servers = np.asarray(servers)
    dem = load_module("traffic", "all_to_all").demand(
        servers, np.random.default_rng(0))
    total = servers.sum()
    intra = servers * (servers - 1) / (total - 1)
    assert np.all(np.diag(dem) == 0.0)
    np.testing.assert_allclose(dem.sum(axis=1) + intra, servers, rtol=1e-12)
    np.testing.assert_allclose(dem.sum(axis=0) + intra, servers, rtol=1e-12)
    u, v = 0, int(np.flatnonzero(servers)[-1])
    assert dem[u, v] == pytest.approx(servers[u] * servers[v] / (total - 1))

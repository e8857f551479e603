"""ApspBackend registry: blocked Floyd-Warshall vs repeated squaring vs
the sparse-frontier ELL Bellman-Ford backend.

Every backend must produce the same distances, and — because they share
ONE fixed-point adjoint (``repro.core.apsp``; ``"ell-bf"`` routes the
same walk through the ELL-aware flavor) — the same SP-DAG subgradients,
tie-splitting included.  Weights quantized to multiples of 1/8 make
float32 path sums exact, so those checks can demand bit-equality rather
than tolerances.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis import given, settings, st

from repro.core import apsp as apsp_mod
from repro.core import graphs, mcf, traffic
from repro.core.apsp import _INF, apsp, normalize_backend, resolve_backend
from repro.core.graphs import (biased_two_cluster_graph, degree_stats,
                               random_regular_ell, random_regular_graph)
from repro.kernels import ell as kell
from repro.kernels import fw as kfw
from repro.kernels import minplus


def _quantize(x):
    """Round to multiples of 1/8: float32-exact adds along any short path."""
    return np.round(np.asarray(x) * 8.0) / 8.0


def _ell_d_max(w):
    """Host-side table width of a dense weight matrix: max in-degree of
    the finite off-diagonal pattern (what ``graphs.degree_stats`` gives
    the solvers)."""
    a = np.asarray(w)
    fin = (a < _INF / 2) & ~np.eye(a.shape[0], dtype=bool)
    return max(1, int(fin.sum(axis=0).max()))


def _apsp_ell(w, **kw):
    return apsp(w, "ell-bf", None, _ell_d_max(w), **kw)


def _w_random(n, seed, p=0.35):
    """Random digraph lengths with _INF non-edges (reachability not
    guaranteed — backends must agree on unreachable pairs too)."""
    rng = np.random.default_rng(seed)
    w = _quantize(rng.uniform(0.5, 8.0, (n, n)))
    w = np.where(rng.random((n, n)) < p, w, _INF)
    np.fill_diagonal(w, 0.0)
    return jnp.asarray(w, jnp.float32)


def _w_topo(topo):
    cap = np.asarray(topo.cap)
    w = np.where(cap > 0, 1.0, _INF)
    np.fill_diagonal(w, 0.0)
    return jnp.asarray(w, jnp.float32)


def _w_cases():
    return {
        "random-sparse": _w_random(24, 0),
        "rrg-unit": _w_topo(random_regular_graph(32, 4, seed=1)),
        "two-cluster": _w_topo(biased_two_cluster_graph(
            [5] * 12, [3] * 12, 0.5, seed=2)),
    }


# ---------------------------------------------------------------------------
# forward: distances
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(_w_cases()))
def test_distances_bit_equal_across_backends(case):
    w = _w_cases()[case]
    d_sq = np.asarray(apsp(w, "squaring"))
    d_fw = np.asarray(apsp(w, "blocked-fw"))
    d_el = np.asarray(_apsp_ell(w))
    assert np.array_equal(d_sq, d_fw), \
        "squaring and blocked-fw disagree on quantized weights"
    assert np.array_equal(d_sq, d_el), \
        "ell-bf disagrees with the dense backends on quantized weights"


@pytest.mark.parametrize("case", sorted(_w_cases()))
def test_distances_match_scipy(case):
    sp = pytest.importorskip("scipy.sparse.csgraph")
    w = np.asarray(_w_cases()[case], np.float64)
    ref = sp.floyd_warshall(np.where(w > _INF / 2, np.inf, w))
    d = np.asarray(apsp(jnp.asarray(w, jnp.float32), "blocked-fw"))
    reach = np.isfinite(ref)
    assert np.all(d[~reach] > _INF / 2), "unreachable pairs must stay +inf"
    np.testing.assert_allclose(d[reach], ref[reach], rtol=1e-6, atol=1e-5)


def test_padded_lanes_leave_valid_block_unchanged():
    """Padding with _INF rows/cols (what n_valid lanes do) must not leak
    into the valid block on any backend."""
    w = _w_cases()["random-sparse"]
    n, m = w.shape[0], 40
    wp = np.full((m, m), _INF, np.float32)
    wp[:n, :n] = np.asarray(w)
    np.fill_diagonal(wp, 0.0)
    wp = jnp.asarray(wp)
    for backend in ("squaring", "blocked-fw", "ell-bf"):
        if backend == "ell-bf":
            d = np.asarray(_apsp_ell(w))
            dp = np.asarray(_apsp_ell(wp))
        else:
            d = np.asarray(apsp(w, backend))
            dp = np.asarray(apsp(wp, backend))
        assert np.array_equal(dp[:n, :n], d), backend
        off = ~np.eye(m - n, dtype=bool)
        assert np.all(dp[n:, n:][off] > _INF / 2), "padding stayed isolated"


def test_auto_matches_explicit_backends():
    w = _w_cases()["rrg-unit"]
    assert np.array_equal(np.asarray(apsp(w, "auto")),
                          np.asarray(apsp(w, "squaring")))


# ---------------------------------------------------------------------------
# backward: the shared SP-DAG subgradient
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", sorted(_w_cases()))
def test_subgradients_identical_across_backends(case):
    w = _w_cases()[case]
    n = w.shape[0]
    d_max = _ell_d_max(w)
    rng = np.random.default_rng(7)
    g = jnp.asarray(_quantize(rng.uniform(0.5, 2.0, (n, n))), jnp.float32)

    def loss(w, backend):
        dm = d_max if backend == "ell-bf" else None
        d = apsp(w, backend, None, dm)
        return jnp.sum(d * jnp.where(d < _INF / 2, g, 0.0))

    g_sq = np.asarray(jax.grad(loss)(w, "squaring"))
    g_fw = np.asarray(jax.grad(loss)(w, "blocked-fw"))
    g_el = np.asarray(jax.grad(loss)(w, "ell-bf"))
    assert np.array_equal(g_sq, g_fw), \
        "the shared adjoint must not depend on which forward ran"
    assert np.array_equal(g_sq, g_el), \
        "the ELL-aware adjoint must route bit-identical subgradients"
    # non-edges carry no subgradient
    assert np.all(g_sq[np.asarray(w) > _INF / 2] == 0.0)


def test_grad_is_unit_flow_on_shortest_paths():
    """Cotangent 1 on pair (0, 2) of the path 0-1-2 deposits unit flow on
    BOTH hops (gradient mass = path hop count)."""
    w = np.full((3, 3), _INF, np.float32)
    np.fill_diagonal(w, 0.0)
    w[0, 1] = w[1, 0] = 1.0
    w[1, 2] = w[2, 1] = 1.0

    def loss(w):
        return apsp(jnp.asarray(w), "blocked-fw")[0, 2]

    g = np.asarray(jax.grad(loss)(w))
    assert g[0, 1] == 1.0 and g[1, 2] == 1.0
    assert g.sum() == 2.0


def test_grad_splits_ties_evenly():
    """Two equal-length 2-hop routes: each carries half the unit flow on
    every backend."""
    w = np.full((4, 4), _INF, np.float32)
    np.fill_diagonal(w, 0.0)
    for a, b in [(0, 1), (0, 2), (1, 3), (2, 3)]:
        w[a, b] = w[b, a] = 1.0
    for backend in ("squaring", "blocked-fw", "ell-bf"):
        dm = 2 if backend == "ell-bf" else None
        g = np.asarray(jax.grad(
            lambda w: apsp(jnp.asarray(w), backend, None, dm)[0, 3])(w))
        np.testing.assert_allclose(g[0, 1], 0.5)
        np.testing.assert_allclose(g[1, 3], 0.5)
        np.testing.assert_allclose(g.sum(), 2.0)


@settings(max_examples=10)
@given(st.sampled_from([8, 12, 16]), st.integers(0, 99))
def test_backend_agreement_property(n, seed):
    w = _w_random(n, seed)
    d_sq = np.asarray(apsp(w, "squaring"))
    d_fw = np.asarray(apsp(w, "blocked-fw"))
    d_el = np.asarray(_apsp_ell(w))
    assert np.array_equal(d_sq, d_fw)
    assert np.array_equal(d_sq, d_el)


# ---------------------------------------------------------------------------
# ELL tables: sentinel pin, round-trips, validation
# ---------------------------------------------------------------------------

def test_ell_inf_sentinel_matches_apsp():
    """graphs (numpy-pure) and apsp must agree on the non-edge sentinel."""
    assert graphs._ELL_INF == _INF


def _topo_families():
    return {
        "rrg": random_regular_graph(24, 4, seed=0),
        "two-cluster": biased_two_cluster_graph([5] * 12, [3] * 12, 0.5,
                                                seed=2),
        "power-law": graphs.random_graph_from_degrees(
            graphs.power_law_degrees(20, 3, 8, 2.5, seed=4), seed=5),
    }


@pytest.mark.parametrize("family", sorted(_topo_families()))
def test_to_ell_round_trips_every_family(family):
    topo = _topo_families()[family]
    n = topo.n
    g = topo.to_ell()
    g.validate()
    want = np.where(np.asarray(topo.cap) > 0, 1.0, _INF).astype(np.float32)
    np.fill_diagonal(want, 0.0)
    assert np.array_equal(g.to_dense(), want)
    # asymmetric per-link lengths survive the round trip too
    rng = np.random.default_rng(9)
    lengths = _quantize(rng.uniform(0.5, 4.0, (n, n))).astype(np.float32)
    g2 = topo.to_ell(lengths=lengths)
    g2.validate()
    want2 = np.where(np.asarray(topo.cap) > 0, lengths, _INF)
    np.fill_diagonal(want2, 0.0)
    assert np.array_equal(g2.to_dense(), want2.astype(np.float32))
    # the traceable packer produces the same tables from the dense matrix
    idx, wgt = apsp_mod._pack_ell(jnp.asarray(want2, jnp.float32), g2.d_max)
    assert np.array_equal(np.asarray(idx), g2.idx)
    assert np.array_equal(np.asarray(wgt), g2.wgt)


def test_to_ell_rejects_truncating_d_max():
    topo = random_regular_graph(16, 4, seed=0)
    with pytest.raises(ValueError, match="silently drop"):
        topo.to_ell(d_max=3)


def test_degree_stats_matches_table_width():
    for family, topo in sorted(_topo_families().items()):
        d_max, mean = degree_stats(topo.cap)
        assert d_max == topo.to_ell().d_max, family
        deg = (np.asarray(topo.cap) > 0).sum(axis=1)
        assert mean == pytest.approx(deg[deg > 0].mean()), family


def test_random_regular_ell_matches_scipy():
    sp = pytest.importorskip("scipy.sparse.csgraph")
    g = random_regular_ell(64, 4, seed=3)
    g.validate()
    assert g.d_max == 4
    w = np.asarray(g.to_dense(), np.float64)
    ref = sp.floyd_warshall(np.where(w > _INF / 2, np.inf, w))
    d, _ = kell.ell_bf_apsp(jnp.asarray(g.idx), jnp.asarray(g.wgt))
    np.testing.assert_allclose(np.asarray(d), ref, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# the ell-bf backend: convergence, kernels, padded-chunk regression
# ---------------------------------------------------------------------------

def test_ell_bf_converges_within_diameter_plus_one():
    """The relaxation is at least one hop of progress per round, so the
    fixed point lands in <= diameter + 1 rounds (the +1 detects it)."""
    for n, r, seed in ((32, 4, 0), (64, 4, 1), (48, 6, 2)):
        g = random_regular_ell(n, r, seed=seed)
        d, rounds = kell.ell_bf_apsp(jnp.asarray(g.idx), jnp.asarray(g.wgt))
        d = np.asarray(d)
        assert np.all(d < _INF / 2), "r-regular construction is connected"
        diameter = int(d.max())   # unit weights: distance = hop count
        assert int(rounds) <= diameter + 1, (n, r, seed)


def test_ell_bf_max_rounds_caps_compile_key():
    g = random_regular_ell(32, 4, seed=0)
    full, _ = kell.ell_bf_apsp(jnp.asarray(g.idx), jnp.asarray(g.wgt))
    capped, rounds = kell.ell_bf_apsp(jnp.asarray(g.idx),
                                      jnp.asarray(g.wgt), max_rounds=2)
    assert int(rounds) <= 2
    # a 2-round cap covers exactly the <= 3-hop pairs (init is one hop)
    d = np.asarray(full)
    c = np.asarray(capped)
    assert np.array_equal(c[d <= 3], d[d <= 3])


def test_ell_bf_streamed_matches_full_solve():
    g = random_regular_ell(64, 4, seed=5)
    d_full, _ = kell.ell_bf_apsp(jnp.asarray(g.idx), jnp.asarray(g.wgt))
    d_str, rounds = kell.ell_bf_apsp_streamed(g.idx, g.wgt, block=16)
    assert np.array_equal(d_str, np.asarray(d_full))
    assert rounds >= 1


def _ell_weighted(n, d, seed):
    """Degree-``d`` ELL tables with random, asymmetric, non-tied lengths
    (the pads keep their ``_INF``)."""
    g = random_regular_ell(n, d, seed=seed)
    rng = np.random.default_rng(seed)
    wgt = np.where(g.wgt < _INF / 2,
                   rng.uniform(0.5, 2.0, g.wgt.shape), g.wgt)
    return jnp.asarray(g.idx), jnp.asarray(wgt, jnp.float32)


def test_ell_pallas_round_matches_jacobi_reference():
    """One round of the Pallas flavor (interpret mode) == the plain
    Jacobi update min(m, min_j wgt[:, j] + m[idx[:, j], :]).  N=40 is no
    multiple of the kernel's target tile, so the padding and crop run."""
    idx, wgt = _ell_weighted(40, 4, seed=7)
    assert 40 % kell._pallas_tile(4)
    m = np.asarray(kell._full_init(idx, wgt))
    # cand[t, j, s] = wgt[t, j] + m[idx[t, j], s]
    cand = np.asarray(wgt)[:, :, None] + m[np.asarray(idx)]
    ref = np.minimum(m, cand.min(axis=1))
    d, rounds = kell.ell_bf_apsp(idx, wgt, max_rounds=1, use_pallas=True,
                                 interpret=True)
    assert int(rounds) == 1
    assert np.array_equal(np.asarray(d).T, ref)


@pytest.mark.parametrize("max_rounds", [1, 2, None])
def test_ell_bf_pallas_flavor_matches_jnp(max_rounds):
    """The Pallas flavor the chip runs (interpret mode, N=40 padded to a
    whole tile, sources to a whole slab) gives the jnp flavor's
    distances bit for bit, round count included, alone and under vmap.
    With one tile the jnp sweep is Jacobi too, so capped runs agree."""
    cases = [_ell_weighted(40, 4, seed=s) for s in (3, 4)]
    kw = dict(max_rounds=max_rounds, interpret=True)
    ref = [kell.ell_bf_apsp(i, w, **kw) for i, w in cases]
    for (i, w), (d_ref, r_ref) in zip(cases, ref):
        d, r = kell.ell_bf_apsp(i, w, use_pallas=True, **kw)
        assert np.array_equal(np.asarray(d), np.asarray(d_ref))
        assert int(r) == int(r_ref)
    idx = jnp.stack([i for i, _ in cases])
    wgt = jnp.stack([w for _, w in cases])
    d, r = jax.vmap(functools.partial(kell.ell_bf_apsp_impl, use_pallas=True,
                                      **kw))(idx, wgt)
    for k, (d_ref, r_ref) in enumerate(ref):
        assert np.array_equal(np.asarray(d[k]), np.asarray(d_ref))
        assert int(r[k]) == int(r_ref)


def test_ell_bf_requires_static_d_max():
    w = _w_cases()["rrg-unit"]
    with pytest.raises(ValueError, match="d_max"):
        apsp(w, "ell-bf")


def test_sp_dag_grad_padded_chunks_bit_identical(monkeypatch):
    """Regression (PR 8): ``_sp_dag_grad`` used to relax the fully-padded
    all-_INF chunk rows; masked-out chunking must not perturb bits.  A
    tiny element budget forces c=5 on n=24 (pad=1) for the dense adjoint
    and a narrow target chunk for the ELL one; both must reproduce the
    unchunked subgradients exactly."""
    w = _w_cases()["random-sparse"]
    n = w.shape[0]
    d_max = _ell_d_max(w)
    d = apsp(w, "squaring")
    rng = np.random.default_rng(11)
    g = jnp.asarray(_quantize(rng.uniform(0.5, 2.0, (n, n))), jnp.float32)
    g = jnp.where(d < _INF / 2, g, 0.0)
    ref_dense = np.asarray(apsp_mod._sp_dag_grad(w, d, g))
    ref_ell = np.asarray(apsp_mod._sp_dag_grad_ell(w, d, g, d_max))
    assert np.array_equal(ref_dense, ref_ell)
    monkeypatch.setattr(apsp_mod, "_BWD_ELEMS", n * n * 5)  # c=5, pad=1
    pad_dense = np.asarray(apsp_mod._sp_dag_grad(w, d, g))
    monkeypatch.setattr(apsp_mod, "_BWD_ELEMS", n * d_max * 5)
    pad_ell = np.asarray(apsp_mod._sp_dag_grad_ell(w, d, g, d_max))
    assert np.array_equal(pad_dense, ref_dense), \
        "dense adjoint changed bits under chunk padding"
    assert np.array_equal(pad_ell, ref_ell), \
        "ELL adjoint changed bits under chunk padding"


def _w_hub(n, seed):
    """A ring 0 -> 1 -> ... -> n-1 -> 0 plus a hub 0 -> t for every t:
    predecessor 0 holds a slot in n - 1 target rows while the table is
    only d_max = 2 wide."""
    rng = np.random.default_rng(seed)
    w = np.full((n, n), _INF, np.float32)
    t = np.arange(n)
    w[t, (t + 1) % n] = _quantize(rng.uniform(1.0, 4.0, n))
    # short hub edges put the hub on most shortest paths, so its column
    # of the one-hop cotangent sums many rounded terms
    w[0, 1:] = _quantize(rng.uniform(0.5, 1.5, n - 1))
    np.fill_diagonal(w, 0.0)
    return jnp.asarray(w)


def _signed_cotangent(d, seed):
    """Unquantized cotangents of both signs: every add rounds, so only
    the same accumulation order gives the same bits."""
    n = d.shape[0]
    g = np.random.default_rng(seed).normal(size=(n, n)).astype(np.float32)
    return jnp.where(d < _INF / 2, jnp.asarray(g), 0.0)


_FOLD_CASES = ("hub", "signed-random-sparse", "signed-rrg-unit",
               "signed-two-cluster", "split-chunks", "vmap-lanes")


@pytest.mark.parametrize("case", _FOLD_CASES)
def test_ell_fold_matches_dense_adjoint(case, monkeypatch):
    """The ELL adjoint folds each predecessor's slots in ascending target
    order; it must give the dense adjoint's bits when a predecessor feeds
    more targets than the table is wide, under signed unquantized
    cotangents, when a predecessor's slots span two chunks, and under
    vmap over lanes whose largest slot counts differ."""
    if case == "vmap-lanes":
        ws = [_w_hub(20, 3), _w_topo(random_regular_graph(20, 4, seed=4))]
        d_max = max(_ell_d_max(w) for w in ws)
        ds = [apsp(w, "squaring") for w in ws]
        gs = [_signed_cotangent(d, 5 + i) for i, d in enumerate(ds)]
        ref = [np.asarray(apsp_mod._sp_dag_grad(*a)) for a in zip(ws, ds, gs)]
        got = np.asarray(jax.vmap(
            lambda w, d, g: apsp_mod._sp_dag_grad_ell(w, d, g, d_max))(
                jnp.stack(ws), jnp.stack(ds), jnp.stack(gs)))
        for i in range(2):
            assert np.array_equal(got[i], ref[i]), f"lane {i}"
        return
    w = (_w_hub(32, 1) if case in ("hub", "split-chunks")
         else _w_cases()[case.removeprefix("signed-")])
    n = w.shape[0]
    d_max = _ell_d_max(w)
    d = apsp(w, "squaring")
    g = _signed_cotangent(d, 9)
    ref = np.asarray(apsp_mod._sp_dag_grad(w, d, g))
    if case == "split-chunks":
        # c = 20 on n = 32: the hub's 31 slots fall in two chunks, the
        # second padded by 8 targets
        monkeypatch.setattr(apsp_mod, "_BWD_ELEMS", n * d_max * 20)
        assert apsp_mod._bwd_chunk(n, d_max) == 20
    got = np.asarray(apsp_mod._sp_dag_grad_ell(w, d, g, d_max))
    assert np.array_equal(got, ref)
    assert np.any(ref != 0.0)


def test_ell_bf_vmaps_like_dense_backends():
    ws = jnp.stack([_w_topo(random_regular_graph(16, 4, seed=s))
                    for s in range(3)])
    d_max = _ell_d_max(ws[0])

    def solve(w):
        return apsp(w, "ell-bf", None, d_max)

    batched = np.asarray(jax.vmap(solve)(ws))
    for i in range(ws.shape[0]):
        assert np.array_equal(batched[i], np.asarray(solve(ws[i])))
        assert np.array_equal(batched[i],
                              np.asarray(apsp(ws[i], "squaring")))


# ---------------------------------------------------------------------------
# the tiled Pallas kernel itself (4-phase path, interpret mode)
# ---------------------------------------------------------------------------

def test_fw_pallas_tiles_match_jnp():
    w = _w_random(32, 3)
    tiled = kfw.fw_apsp_pallas(w, t=8, interpret=True)   # 4x4 tiles
    plain = kfw.fw_apsp_jnp(w)
    assert np.array_equal(np.asarray(tiled), np.asarray(plain))


def test_fw_pallas_single_tile_fast_path():
    w = _w_random(16, 4)
    one = kfw.fw_apsp_pallas(w, t=16, interpret=True)
    assert np.array_equal(np.asarray(one), np.asarray(kfw.fw_apsp_jnp(w)))


def test_fw_pallas_validates_shapes():
    with pytest.raises(ValueError, match="square"):
        kfw.fw_apsp_pallas(jnp.zeros((8, 12)), t=4, interpret=True)
    with pytest.raises(ValueError, match="multiple of the"):
        kfw.fw_apsp_pallas(jnp.zeros((10, 10)), t=4, interpret=True)


# ---------------------------------------------------------------------------
# registry plumbing + solver integration
# ---------------------------------------------------------------------------

def test_normalize_backend_mapping():
    assert normalize_backend(None, use_pallas=False) == "auto"
    assert normalize_backend(None, use_pallas=True) == "squaring-pallas"
    assert normalize_backend(True) == "squaring-pallas"    # legacy bool slot
    assert normalize_backend(False) == "squaring"
    assert normalize_backend("blocked-fw") == "blocked-fw"
    with pytest.raises(ValueError, match="unknown APSP backend"):
        normalize_backend("dijkstra")


def test_resolve_backend_threshold_is_static():
    thr = apsp_mod.AUTO_THRESHOLD
    assert resolve_backend("auto", thr) == "blocked-fw"
    assert resolve_backend("auto", thr - 1) == "squaring"
    assert resolve_backend("squaring", thr) == "squaring"


def test_resolve_backend_goes_sparse_with_density():
    thr, sparse = apsp_mod.AUTO_THRESHOLD, apsp_mod.SPARSE_THRESHOLD
    assert resolve_backend("auto", thr, mean_degree=sparse) == "ell-bf"
    assert resolve_backend("auto", thr, mean_degree=sparse + 1.0) \
        == "blocked-fw"
    # density never overrides the small-n dense pick or an explicit name
    assert resolve_backend("auto", thr - 1, mean_degree=4.0) == "squaring"
    assert resolve_backend("blocked-fw", thr, mean_degree=4.0) \
        == "blocked-fw"


def test_resolve_backend_density_keeps_dense_keys_unchanged():
    """Host-side density resolution must not churn dense jit/AOT cache
    keys: dense outcomes pass the name through verbatim with d_max None;
    only a sparse resolution returns a concrete ("ell-bf", width)."""
    cap = np.asarray(random_regular_graph(24, 4, seed=0).cap)
    assert mcf.resolve_backend_density("auto", cap, n=24) == ("auto", None)
    assert mcf.resolve_backend_density("squaring", cap, n=9999) \
        == ("squaring", None)
    bk, d_max = mcf.resolve_backend_density(
        "auto", cap, n=apsp_mod.AUTO_THRESHOLD)
    assert (bk, d_max) == ("ell-bf", 4)
    # caller-supplied hints skip the capacity scan entirely
    assert mcf.resolve_backend_density(
        "ell-bf", None, n=4096, d_max=16) == ("ell-bf", 16)


def test_solve_dual_matches_across_backends():
    topo = random_regular_graph(16, 4, seed=0, servers=3)
    dem = traffic.make("permutation", topo.servers, seed=1)
    r_sq = mcf.solve_dual(topo, dem, iters=80, backend="squaring")
    r_fw = mcf.solve_dual(topo, dem, iters=80, backend="blocked-fw")
    r_el = mcf.solve_dual(topo, dem, iters=80, backend="ell-bf")
    # identical distances + identical subgradients => identical descent
    # on the dense pair; ell-bf sums path lengths in a different order,
    # so unquantized descent weights cost it ~1 ulp per hop
    assert r_fw.throughput_ub == pytest.approx(r_sq.throughput_ub,
                                               rel=1e-5)
    assert r_el.throughput_ub == pytest.approx(r_sq.throughput_ub,
                                               rel=1e-5)
    assert r_fw.iterations == r_sq.iterations


# ---------------------------------------------------------------------------
# minplus kernel validation (was: bare asserts)
# ---------------------------------------------------------------------------

def test_minplus_matmul_pallas_raises_on_bad_inputs():
    with pytest.raises(ValueError, match="inner dimensions disagree"):
        minplus.minplus_matmul_pallas(jnp.zeros((128, 128)),
                                      jnp.zeros((256, 128)),
                                      interpret=True)
    with pytest.raises(ValueError, match="callers pad"):
        minplus.minplus_matmul_pallas(jnp.zeros((100, 128)),
                                      jnp.zeros((128, 128)),
                                      interpret=True)

"""Persistent AOT compile cache (``repro.core.aotcache``): hits serve
serialized executables with zero new XLA compiles, a bad entry is
recompiled, a program that cannot be lowered raises, and results are
identical either way."""
from pathlib import Path

import numpy as np
import pytest

from repro.core import aotcache, mcf, traffic
from repro.core.engine import get_engine
from repro.core.graphs import random_regular_graph
from repro.core.plan import compile_cache_sizes


@pytest.fixture(autouse=True)
def _fresh_counters():
    aotcache.reset_stats()
    yield
    aotcache.reset_stats()


def _instance(n=16, servers=3, seed=0):
    t = random_regular_graph(n, 4, seed=seed, servers=servers)
    return t, traffic.make("permutation", t.servers, seed=seed + 1)


def test_miss_then_hit_same_results(tmp_path):
    t, dem = _instance()
    plain = get_engine("dual", iters=50).solve_batch([t] * 2, [dem] * 2)
    eng = get_engine("dual", iters=50, aot_cache=str(tmp_path))
    first = eng.solve_batch([t] * 2, [dem] * 2)
    assert aotcache.stats() == {"compiles": 1, "hits": 0, "misses": 1,
                                "errors": 0}
    second = eng.solve_batch([t] * 2, [dem] * 2)
    assert aotcache.stats()["hits"] == 1
    assert aotcache.stats()["compiles"] == 1
    for a, b, c in zip(plain, first, second):
        assert a.throughput == b.throughput == c.throughput
    assert len(eng._aot.entries()) == 1


def test_second_cache_instance_hits_without_compiling(tmp_path):
    """A fresh AotCache over the same directory (the in-process stand-in
    for a warm process) serves the entry with zero new compiles."""
    t, dem = _instance()
    get_engine("certified", iters=50,
               aot_cache=str(tmp_path)).solve_batch([t], [dem])
    compiled = aotcache.stats()["compiles"]
    assert compiled >= 1
    warm = get_engine("certified", iters=50, aot_cache=str(tmp_path))
    res = warm.solve_batch([t], [dem])
    s = aotcache.stats()
    assert s["compiles"] == compiled, "warm run must not compile"
    assert s["hits"] >= 1
    assert np.isfinite(res[0].throughput)


def test_different_shapes_get_different_entries(tmp_path):
    t1, d1 = _instance(16)
    t2, d2 = _instance(24, seed=3)
    eng = get_engine("dual", iters=50, bucket=None, aot_cache=str(tmp_path))
    eng.solve_batch([t1], [d1])
    eng.solve_batch([t2], [d2])
    assert len(eng._aot.entries()) == 2


def test_other_source_never_hits(tmp_path, monkeypatch):
    """A cache that outlives a code change (a persistent
    ``JAX_COMPILATION_CACHE_DIR``) must not serve the old program."""
    t, dem = _instance()
    get_engine("dual", iters=20, aot_cache=str(tmp_path)).solve_batch(
        [t], [dem])
    monkeypatch.setattr(aotcache, "source_digest", lambda: "edited")
    get_engine("dual", iters=20, aot_cache=str(tmp_path)).solve_batch(
        [t], [dem])
    assert aotcache.stats() == {"compiles": 2, "hits": 0, "misses": 2,
                                "errors": 0}
    assert len(list(tmp_path.glob("*.aot"))) == 2


def test_corrupt_entry_falls_back_and_heals(tmp_path):
    t, dem = _instance()
    eng = get_engine("dual", iters=50, aot_cache=str(tmp_path))
    ref = eng.solve_batch([t], [dem])[0].throughput
    blob = next(iter(tmp_path.glob("*.aot")))
    blob.write_bytes(b"not a pickle")
    with pytest.warns(RuntimeWarning, match="stale/corrupt"):
        res = eng.solve_batch([t], [dem])[0].throughput
    assert res == ref
    assert aotcache.stats()["errors"] == 1
    # the poisoned entry was dropped and rebuilt
    assert aotcache.stats()["compiles"] == 2
    assert len(eng._aot.entries()) == 1


def test_solver_level_fallback_on_unloadable_function(tmp_path):
    """aot.call on something that cannot be lowered raises instead of
    running some other path: a refused kernel must never be hidden."""
    cache = aotcache.AotCache(tmp_path)
    calls = []

    def plain(x, *, k):
        calls.append(x)
        return x * k

    with pytest.raises(AttributeError, match="lower"):
        cache.call(plain, ("test",), (3,), {"k": 2})
    assert calls == []
    assert aotcache.stats() == {"compiles": 0, "hits": 0, "misses": 1,
                                "errors": 0}
    assert cache.entries() == []


def test_entries_record_custom_calls(tmp_path):
    """Each entry records its program's custom-call targets, on the miss
    and again when a fresh instance serves the hit."""
    t, dem = _instance()
    cache = aotcache.AotCache(tmp_path)
    get_engine("dual", iters=20, aot_cache=cache).solve_batch([t], [dem])
    assert cache.last["tag"] == ("dual", "donated")
    assert "tpu_custom_call" not in cache.last["custom_calls"]
    warm = aotcache.AotCache(tmp_path)
    get_engine("dual", iters=20, aot_cache=warm).solve_batch([t], [dem])
    assert aotcache.stats()["hits"] == 1
    assert warm.last == cache.last


def test_default_dirs_stay_in_checkout_or_env(monkeypatch, tmp_path):
    monkeypatch.delenv("REPRO_AOT_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = Path(__file__).resolve().parents[1]
    assert aotcache.cache_root() == checkout / ".jax_cache"
    assert aotcache.default_dir().is_relative_to(checkout)
    # an installed copy has no checkout to default to: it says so
    monkeypatch.setattr(aotcache, "__file__",
                        str(tmp_path / "site-packages/repro/core/aotcache.py"))
    with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
        aotcache.cache_root()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert aotcache.cache_root() == tmp_path
    assert aotcache.default_dir().is_relative_to(tmp_path)


def test_resolve_knob_and_env(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_AOT_CACHE", raising=False)
    assert aotcache.resolve(None) is None
    assert aotcache.resolve(False) is None
    c = aotcache.resolve(str(tmp_path))
    assert isinstance(c, aotcache.AotCache) and c.dir == tmp_path
    monkeypatch.setenv("REPRO_AOT_CACHE", "1")
    monkeypatch.setenv("REPRO_AOT_CACHE_DIR", str(tmp_path / "env"))
    env_cache = aotcache.resolve(None)
    assert env_cache is not None and env_cache.dir == tmp_path / "env"
    monkeypatch.setenv("REPRO_AOT_CACHE", "off")
    assert aotcache.resolve(None) is None


def test_compile_cache_sizes_carries_aot_counters(tmp_path):
    sizes = compile_cache_sizes()
    assert sizes["aot.compiles"] == 0 and sizes["aot.hits"] == 0
    t, dem = _instance()
    eng = get_engine("dual", iters=50, aot_cache=str(tmp_path))
    eng.solve_batch([t], [dem])
    eng.solve_batch([t], [dem])
    sizes = compile_cache_sizes()
    assert sizes["aot.compiles"] == 1 and sizes["aot.hits"] == 1


def test_single_solve_ignores_aot(tmp_path):
    t, dem = _instance()
    res = mcf.solve_dual(t, dem, iters=50,
                         aot=aotcache.AotCache(tmp_path))
    assert np.isfinite(res.throughput_ub)
    assert aotcache.stats() == {"compiles": 0, "hits": 0, "misses": 0,
                                "errors": 0}

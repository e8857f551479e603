"""Persistent AOT compile cache for the batched solvers.

``plan.BatchPlan`` already deduplicates compiles *within* a process (one
XLA program per (bucket, chunk-shape, solver-config)), but every fresh
process pays the full jit wall again — the fig6-style cold-start tax.
This module serializes compiled executables to disk so a warm process
skips XLA entirely:

* ``AotCache(dir).call(jitfn, tag, args, static_kw)`` — look up the
  executable keyed by (jax version, backend, device kind/count, a digest
  of this package's source, tag, arg shapes/dtypes, static kwargs), so
  an entry compiled from other code never matches.  On a hit the
  serialized executable is deserialized and invoked; on a miss the
  function is lowered + compiled ahead-of-time, serialized to the cache
  directory, then invoked.  A stale or corrupt entry is dropped and
  recompiled, and a blob that cannot be written is skipped; both count
  as ``errors``.  A program that fails to lower or compile raises — the
  cache never swaps a kernel the compiler refused for some other path.
  Each entry
  records the compiled program's custom-call targets and its device op
  scopes (``spans.scopes_of_hlo``); ``last`` holds those of the most
  recent call (``"tpu_custom_call"`` = a Mosaic kernel ran).
* ``dispatch(fn, tag, args, static_kw, aot=, sharding=)`` — how every
  batch solver runs its program: through ``aot`` when given and the call
  is unsharded, else as a plain jit call; either way the program is
  noted for ``spans.op_scopes()``.
* ``resolve(knob)`` — map an engine-level knob (None / bool / directory
  path / ``AotCache``) to an ``AotCache`` or ``None``.  ``None`` defers
  to the ``REPRO_AOT_CACHE`` env var (truthy enables;
  ``REPRO_AOT_CACHE_DIR`` overrides the location), so CI can flip the
  cache on without touching call sites.
* ``cache_root()`` / ``enable_jax_cache()`` — where compiled programs
  live: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself),
  else ``<checkout>/.jax_cache``.  The AOT blobs sit in its
  ``repro-aot`` subdirectory, so nothing lands outside the checkout
  unless that variable says so.
* module-level counters (``stats()``) — ``compiles`` / ``hits`` /
  ``misses`` / ``errors``, surfaced through
  ``plan.compile_cache_sizes()`` so benchmark drivers can assert the
  zero-new-compiles warm-run invariant.

Single-device only: sharded executables bake in device assignments that
do not survive serialization portably, so the engines gate ``aot`` calls
on ``sharding is None``.
"""
from __future__ import annotations

import functools
import hashlib
import os
import pickle
import re
import warnings
from pathlib import Path
from typing import Any, Mapping, Sequence

import jax

from repro.core import spans

__all__ = ["AotCache", "dispatch", "resolve", "default_dir", "cache_root",
           "enable_jax_cache", "stats", "reset_stats"]

_COUNTERS = {"compiles": 0, "hits": 0, "misses": 0, "errors": 0}
_WARNED: set[str] = set()


def stats() -> dict[str, int]:
    """Process-wide cache counters (copies; see module docstring)."""
    return dict(_COUNTERS)


def reset_stats() -> None:
    for k in _COUNTERS:
        _COUNTERS[k] = 0


def _checkout() -> Path:
    """The source checkout this package runs from (``<checkout>/src/repro``).
    An installed copy has none, and must be told where caches go."""
    src = Path(__file__).resolve().parents[2]
    if src.name != "src":
        raise RuntimeError(
            f"repro runs from {src.parent}, not a source checkout's src/; "
            f"set JAX_COMPILATION_CACHE_DIR to say where compiled programs "
            f"are cached")
    return src.parent


def cache_root() -> Path:
    """Directory of every compile cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache`` (a fixed path, since the
    path is part of what a cache entry is found under)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    return Path(env).expanduser() if env else _checkout() / ".jax_cache"


def enable_jax_cache() -> Path:
    """Turn on JAX's persistent compilation cache at ``cache_root()``.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    no other directory is set here."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(cache_root()))
    return cache_root()


def default_dir() -> Path:
    env = os.environ.get("REPRO_AOT_CACHE_DIR")
    if env:
        return Path(env).expanduser()
    return cache_root() / "repro-aot"


def resolve(knob: "bool | str | os.PathLike | AotCache | None"
            ) -> "AotCache | None":
    """Map the engine's ``aot_cache`` knob to a cache instance.

    ``None`` -> env-controlled (``REPRO_AOT_CACHE`` truthy enables),
    ``False`` -> off, ``True`` -> default directory, str/path -> that
    directory, an ``AotCache`` -> itself."""
    if isinstance(knob, AotCache):
        return knob
    if knob is None:
        env = os.environ.get("REPRO_AOT_CACHE", "").strip().lower()
        if env in ("", "0", "false", "off", "no"):
            return None
        knob = True
    if knob is False:
        return None
    if knob is True:
        return AotCache(default_dir())
    return AotCache(Path(knob).expanduser())


def _warn_once(key: str, msg: str) -> None:
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


@functools.lru_cache(maxsize=None)
def source_digest() -> str:
    """sha256 over every ``.py`` file of the ``repro`` package."""
    root = Path(__file__).resolve().parents[1]
    h = hashlib.sha256()
    for p in sorted(root.rglob("*.py")):
        h.update(p.relative_to(root).as_posix().encode())
        h.update(p.read_bytes())
    return h.hexdigest()


def _abstract(x: Any) -> tuple:
    a = jax.api_util.shaped_abstractify(x)
    return (tuple(a.shape), str(a.dtype))


def _signature(tag: Sequence[str], args: Sequence[Any],
               static_kw: Mapping[str, Any]) -> tuple:
    """What one program is compiled for: tag, arg shapes/dtypes, static
    kwargs."""
    return (tuple(tag), tuple(_abstract(a) for a in args),
            tuple(sorted((k, repr(v)) for k, v in static_kw.items())))


def dispatch(fn: Any, tag: Sequence[str], args: Sequence[Any],
             static_kw: Mapping[str, Any], *, aot: "AotCache | None" = None,
             sharding: Any = None) -> Any:
    """Run a batch solver's program ``fn(*args, **static_kw)``: through
    ``aot`` when given and ``sharding`` is None (sharded executables are
    not cached), else as the jit call.  The program is noted for
    ``spans.op_scopes()``: by the cache entry's scope map, or by its
    ``Lowered``, whose compile jit's caches serve with the executable
    this call runs."""
    with warnings.catch_warnings():
        # outputs are per-lane scalars, so XLA reports a donation unused —
        # expected, not actionable
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        if aot is not None and sharding is None:
            return aot.call(fn, tag, args, static_kw)
        key = ("jit",) + _signature(tag, args, static_kw) + (repr(sharding),)
        if not spans.noted(key):
            spans.note_program(key, fn.lower(*args, **static_kw).compile)
        return fn(*args, **static_kw)


class AotCache:
    """Directory-backed store of serialized compiled executables."""

    def __init__(self, directory: os.PathLike | str):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.last: dict[str, Any] = {}   # meta of the most recent call

    # -- keying ---------------------------------------------------------
    def _key(self, tag: Sequence[str], args: Sequence[Any],
             static_kw: Mapping[str, Any]) -> str:
        devs = jax.devices()
        fp = repr((
            jax.__version__,
            jax.default_backend(),
            devs[0].device_kind if devs else "none",
            len(devs),
            source_digest(),
        ) + _signature(tag, args, static_kw))
        return hashlib.sha256(fp.encode()).hexdigest()[:32]

    def _path(self, key: str) -> Path:
        return self.dir / f"{key}.aot"

    # -- core -----------------------------------------------------------
    def call(self, jitfn: Any, tag: Sequence[str], args: Sequence[Any],
             static_kw: Mapping[str, Any]) -> Any:
        """Run ``jitfn(*args, **static_kw)`` through the cache.

        Hit: deserialize the stored executable and invoke it on ``args``.
        Miss: ``jitfn.lower(...).compile()``, serialize, store, invoke.
        Lowering and compile errors propagate; cache-side failures (an
        unreadable entry, an unwritable blob) warn once and count."""
        from jax.experimental import serialize_executable as se

        path = self._path(self._key(tag, args, static_kw))
        if path.exists():
            try:
                blob = pickle.loads(path.read_bytes())
                compiled = se.deserialize_and_load(
                    blob["payload"], blob["in_tree"], blob["out_tree"])
                scopes = blob["meta"]["scopes"]
                out = compiled(*args)
            except Exception as e:
                _COUNTERS["errors"] += 1
                _warn_once(f"load:{path.stem}",
                           f"aotcache: stale/corrupt entry {path.name} "
                           f"({e!r}); recompiling")
                path.unlink(missing_ok=True)
            else:
                _COUNTERS["hits"] += 1
                self.last = blob["meta"]
                spans.note_program(path.stem, scopes)
                return out

        _COUNTERS["misses"] += 1
        compiled = jitfn.lower(*args, **static_kw).compile()
        _COUNTERS["compiles"] += 1
        text = compiled.as_text()
        self.last = {"tag": tuple(tag), "custom_calls": sorted(set(
            re.findall(r'custom_call_target="([^"]+)"', text))),
            "scopes": spans.scopes_of_hlo(text)}
        spans.note_program(path.stem, self.last["scopes"])
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            tmp = path.with_suffix(f".tmp{os.getpid()}")
            tmp.write_bytes(pickle.dumps(
                {"payload": payload, "in_tree": in_tree,
                 "out_tree": out_tree, "meta": self.last}))
            os.replace(tmp, path)
        except Exception as e:
            _COUNTERS["errors"] += 1
            _warn_once(f"store:{'/'.join(map(str, tag))}",
                       f"aotcache: could not store {path.name} ({e!r})")
        return compiled(*args)

    def entries(self) -> list[str]:
        return sorted(p.stem for p in self.dir.glob("*.aot"))

"""The benchmark's files, found by name: ``bench/<kind>/<name>.py`` for
code (entries, fabrics, traffic, metrics) and JSON for data."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


_MODULES: dict[tuple[str, str], object] = {}


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded once per process; an unknown
    name is an error."""
    if (kind, name) not in _MODULES:
        path = BENCH / kind / f"{name}.py"
        if not path.is_file():
            raise LookupError(f"no {kind} named {name!r} (bench/{kind}/)")
        spec = importlib.util.spec_from_file_location(
            f"bench.{kind}.{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[kind, name] = mod
    return _MODULES[kind, name]

"""Finding a cell's files by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration
and traffic mix; each lives in a file of its own under this benchmark's
directory: ``configs/<config>.json``, ``traffic/<mix>.json``,
``limits/<cell>.json`` (the comparison's limits), ``metrics/<metric>.py``
(a per-layer metric's reader), ``references/<family>.py`` (a plain
reference of a model family), ``adapters/<family>.py`` (the program's model
at a configuration's sizes) and ``streams/<kind>.py`` (a token stream a mix
names).  Adding a cell adds files; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
PEAKS = BENCH_DIR / "peaks.json"


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell's entry with its configuration, mix, limits and metrics."""
    bench = benchmark(root)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = dict(found[0])
    w["cfg"] = _json(BENCH_DIR / "configs" / f"{w['config']}.json")
    w["mix"] = _json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    lim = BENCH_DIR / "limits" / f"{name}.json"
    w["limits"] = _json(lim) if lim.exists() else None

    def applies(m: Dict[str, Any]) -> bool:
        return "workloads" not in m or name in m["workloads"]

    w["end_to_end"] = [m for m in bench["end_to_end"] if applies(m)]
    w["per_layer"] = [m for m in bench["per_layer"] if applies(m)]
    return w


def module(kind: str, name: str):
    """The module ``<kind>/<name>.py`` under this benchmark's directory."""
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    if spec is None or spec.loader is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``read(run) -> value or None`` from ``metrics/<metric>.py``."""
    return module("metrics", metric).read


def peak(kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind`` ``kind``; a kind
    missing from the table is an error, never a default."""
    table = _json(PEAKS)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS.name}")
    return table[kind]

"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json`` and a per-layer metric the reader
``bench/metrics/<metric>.py``. Adding a cell, a mix or a metric adds files
and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """The workload entry, its configuration file and its traffic file,
    and the metric entries (end-to-end and per-layer) that it reports."""
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in bm["configs"] if c["name"] == w["config"])

    def reports(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return {
        "workload": w,
        "config": _load_json(root / cfg_entry["file"]),
        "traffic": _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        "end_to_end": [m for m in bm["end_to_end"] if reports(m)],
        "per_layer": [m for m in bm["per_layer"] if reports(m)],
    }


def metric_reader(name: str):
    """``read(record) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    module_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind missing
    from the table is an error, never a default."""
    table = _load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]

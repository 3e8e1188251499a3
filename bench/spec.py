"""Finds a cell's pieces by the names in ``BENCHMARK.json`` and in its
data files.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json`` and a per-layer metric the reader
``bench/metrics/<metric>.py``. A configuration's ``generator`` names the
module ``bench/generators/<generator>.py`` that draws its edge tuples, and
each algorithm of a traffic mix names ``bench/algorithms/<alg>.py``, the
benchmark's own reference for that query kind (see :func:`algorithm`).
Adding a cell, a mix, a metric, a graph family or a query kind adds files
and entries; nothing here names one.
"""
from __future__ import annotations

import functools
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


@functools.cache
def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded once by its file."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in path.parent.glob("*.py"))
        raise KeyError(f"no {kind} module {name!r} in bench/{kind}/; "
                       f"known: {known}")
    module_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def metric_reader(name: str):
    """``read(record) -> float | None`` of ``bench/metrics/<name>.py``."""
    return _module("metrics", name).read


def generator(name: str):
    """``bench/generators/<name>.py``: ``tuples(cfg, rng) -> (src, dst,
    n)``, a graph family's edge tuples drawn from ``rng`` (see
    bench/graphgen.py for what is built from them)."""
    return _module("generators", name)


def algorithm(name: str):
    """``bench/algorithms/<name>.py``, the plain reference of one query
    kind. It imports nothing of the program and provides:

    * ``PAYLOAD_FIELD``: the key of the served payload that holds the
      answer;
    * ``answers(rows, cols, n, roots, seed) -> [len(roots), n]``: the
      reference's answers over the directed entries ``rows[i] -> cols[i]``;
      ``seed`` is the run's (it keys any edge weights);
    * ``count_wrong(got, want) -> int``: the comparison of one answer;
    * ``control(rows, cols, n, roots, seed, want)``: the control's answers,
      which break the configuration's guarantee and must fail it;
    * ``frontier_entries(payloads, rows, cols, n, roots, degrees, trips)
      -> list``: per loop trip of a bucket, the stored entries a push
      would have to read (see bench/roofline.py);
    * ``least_bytes(n, nnz, k, frontier_entries) -> float``: the least HBM
      bytes of one bucket of ``k`` queries.

    A kind whose answers are compared within a tolerance also gives
    ``distance(got, want) -> float``, its ``LIMIT`` and the check's name
    ``DISTANCE``; the largest distance is reported beside its limit."""
    return _module("algorithms", name)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind missing
    from the table is an error, never a default."""
    table = _load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]

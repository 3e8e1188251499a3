"""The DIMACS10 random geometric configuration: its generator, its cell,
the reader of ``device_ms_per_trip``, and the comparison on a graph deeper
than 64 levels."""
import json
from math import comb, pi
from pathlib import Path

import numpy as np
import pytest

from bench import graphgen, harness, reference, spec, traffic

CELL = "rgg-bfs-uniform"
CFG = spec.cell(CELL)["config"]
SEED = 2 ** 31 + 13


def _cfg(scale: int, structure_seed: int = 20260116) -> dict:
    return {**CFG, "scale": scale, "structure_seed": structure_seed}


def test_cell_and_generator_resolve():
    c = spec.cell(CELL)
    assert c["workload"]["chips"] == 1
    assert c["config"]["generator"] == "rgg"
    assert c["config"]["radius_factor"] == 0.55
    assert c["config"]["kernels"] == ["bfs"]
    assert set(c["traffic"]["mix"]) == {"bfs"}
    assert callable(spec.generator("rgg").tuples)
    assert "device_ms_per_trip" in [m["name"] for m in c["per_layer"]]
    # every accepted per-layer metric with no list of cells reports here
    assert {"tickets_per_window", "bucket_fill", "traversal_roofline",
            "device_idle_share"} <= {m["name"] for m in c["per_layer"]}


def test_symmetric_simple_and_deterministic():
    rows, cols, n, _perm = graphgen.generate(_cfg(12), SEED)
    assert n == 4096
    assert np.all(rows != cols)
    keys = rows.astype(np.int64) * n + cols
    assert np.unique(keys).size == keys.size
    back = np.sort(cols.astype(np.int64) * n + rows)
    np.testing.assert_array_equal(np.sort(keys), back)
    again = graphgen.structure(_cfg(12))
    for x, y in zip(graphgen.structure(_cfg(12)), again):
        np.testing.assert_array_equal(x, y)
    other = graphgen.structure(_cfg(12, structure_seed=20260117))
    assert not np.array_equal(other[0], again[0])


def test_edge_count_matches_the_geometric_probability():
    """Two uniform points of the unit square lie within r with chance
    pi r^2 - 8 r^3 / 3 + r^4 / 2; the edge count keeps to it within 1%."""
    scale = 16
    rows, _cols, n = graphgen.structure(_cfg(scale))
    r = spec.generator("rgg").radius(n, CFG["radius_factor"])
    want = comb(n, 2) * (pi * r ** 2 - 8 * r ** 3 / 3 + r ** 4 / 2)
    assert abs(rows.size / 2 / want - 1) < 0.01


def test_device_ms_per_trip_reader():
    read = spec.metric_reader("device_ms_per_trip")
    record = json.loads((Path(__file__).parent / "data" / "record.json")
                        .read_text())
    trips = sum(b["trips"] for b in record["buckets"])
    assert trips == 111
    assert read(record) == pytest.approx(1000 * 19.917940136 / 111)
    tiny = {"busy_s": 2.0, "buckets": [{"trips": 300}, {"trips": 100}]}
    assert read(tiny) == pytest.approx(5.0)
    assert read({"busy_s": 2.0, "buckets": []}) is None
    assert read({"buckets": [{"trips": 3}]}) is None


def _records(rows, cols, n, perm, cut=None):
    """Every query of a short stream answered by the reference, each
    answer with its levels beyond ``cut`` unreached when one is given."""
    degrees = np.bincount(rows, minlength=n)
    tf = {**spec.cell(CELL)["traffic"], "rate_per_s": 20.0}
    _warm, stream = traffic.make(tf, degrees, perm, SEED, 4.0)
    roots = [root for _due, _alg, root in stream]
    hops = reference.hop_counts(rows, cols, n, roots)
    out = []
    for i, (due, alg, root) in enumerate(stream):
        got = hops[i].copy()
        if cut is not None:
            got[got > cut] = -1
        out.append({"i": i, "alg": alg, "root": root, "ok": True,
                    "t_due": due, "t_done": due + 0.5,
                    "payload": {"levels": got,
                                "iterations": int(hops[i].max()) + 1}})
    return out, hops


def test_comparison_on_a_graph_deeper_than_64_levels():
    """At scale 14 BFS runs 80-130 levels deep: exact answers pass, and
    the control and answers cut at 64 levels (a traversal stopped at 64
    trips) fail."""
    rows, cols, n, perm = graphgen.generate(_cfg(14), SEED)
    sound, hops = _records(rows, cols, n, perm)
    assert hops.max() > 64
    checks = harness.compare(sound, rows, cols, n, SEED)
    assert checks["bfs_wrong"]["value"] == 0
    assert checks["unanswered"]["value"] == 0
    control = harness.compare(sound, rows, cols, n, SEED, control=True)
    assert control["bfs_wrong"]["value"] > 0
    cut, _hops = _records(rows, cols, n, perm, cut=64)
    assert harness.compare(cut, rows, cols, n, SEED)["bfs_wrong"][
        "value"] > 0

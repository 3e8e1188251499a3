"""Every per-layer reader, and the trace reduction, on a small record and
trace recorded on one TPU v5 lite (kron-bfs-uniform, scale 18; see the
``note`` in each file under data/)."""
import json
from pathlib import Path

import pytest

from bench import devtrace, spec

DATA = Path(__file__).parent / "data"
RECORD = json.loads((DATA / "record.json").read_text())
EVENTS = json.loads((DATA / "trace_events.json").read_text())
PER_LAYER = [m["name"] for m in spec.benchmark()["per_layer"]]


def _read(name, record=RECORD):
    return spec.metric_reader(name)(record)


def test_every_per_layer_metric_has_a_reader():
    for name in PER_LAYER:
        assert callable(spec.metric_reader(name))


def test_counters():
    assert _read("tickets_per_window") == pytest.approx(16.0)
    assert _read("bucket_fill") == pytest.approx(100.0)
    assert _read("cache_hit_share") == 0.0


def test_device_idle_share():
    r = RECORD
    want = 100.0 * (1.0 - r["busy_pending_s"] / r["pending_s"])
    assert _read("device_idle_share") == pytest.approx(want)
    assert _read("device_idle_share") == pytest.approx(0.41623642, rel=1e-6)


def test_traversal_roofline_counts_the_cheaper_direction():
    r = RECORD
    n, nnz = r["n"], r["nnz"]
    least = 0
    for b in r["buckets"]:
        assert b["alg"] == "bfs"     # a pattern matrix: 4 bytes an entry
        pull = nnz * 4 + 4 * (n + 1) + 2 * b["rows"] * n * 4
        least += sum(min(pull, 4 * e) for e in b["frontier_entries"])
    want = 100.0 * least / r["peaks"]["hbm_bytes_per_s"] / r["busy_s"]
    assert _read("traversal_roofline") == pytest.approx(want)
    assert _read("traversal_roofline") == pytest.approx(0.0062541940,
                                                        rel=1e-6)


def test_readers_find_nothing_to_read():
    bare = {k: v for k, v in RECORD.items()
            if k not in ("busy_s", "window_s", "pending_s", "busy_pending_s",
                         "buckets")}
    for name in ("traversal_roofline", "device_idle_share"):
        assert _read(name, bare) is None
    same = {**RECORD, "stats_after": RECORD["stats_before"]}
    for name in ("tickets_per_window", "bucket_fill", "cache_hit_share"):
        assert _read(name, same) is None


def test_trace_reduction():
    red = devtrace.reduce(EVENTS, 1, EVENTS["pending"])
    assert red["window_s"] == pytest.approx(2.0)
    assert red["busy_s"] == pytest.approx(1.996253787)
    assert red["busy_pending_s"] == pytest.approx(red["busy_s"])
    ops = red["breakdown"]["device_ops"]
    assert ops[0] == ["fusion.8 fusion s32[262144,8]",
                      pytest.approx(0.523339829)]
    assert [t for _n, t in ops] == sorted((t for _n, t in ops), reverse=True)
    # self times never exceed the busy time they partition
    assert sum(t for _n, t in ops) <= red["busy_s"] + 1e-9
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0] == ["PjitFunction(convert_element_type)",
                       pytest.approx(0.003314246)]
    assert sum(t for _n, t in gaps) <= red["window_s"] - red["busy_s"] + 1e-9


def test_gaps_without_a_query_outstanding():
    red = devtrace.reduce(EVENTS, 1, [])
    assert red["pending_s"] == 0 and red["busy_pending_s"] == 0
    assert {n for n, _t in red["breakdown"]["idle_gaps"]} == {
        "no query outstanding"}


def test_nested_ops_count_once():
    events = {"host": [("bench/window", 0, 200)],
              "devices": {"/device:TPU:0": [
                  ("%while.1 = (s32[8]) while(...)", 0, 100),
                  ("%fusion.2 = s32[8]{0} fusion(...)", 10, 30),
                  ("%fusion.3 = s32[8]{0} fusion(...)", 40, 90),
                  ("%copy.4 = s32[8]{0} copy(...)", 150, 170)]}}
    red = devtrace.reduce(events, 1, [(0.0, 1e-7)])
    assert red["busy_s"] == pytest.approx(120e-9)
    assert red["pending_s"] == pytest.approx(100e-9)
    assert red["busy_pending_s"] == pytest.approx(100e-9)
    assert dict(red["breakdown"]["device_ops"]) == pytest.approx({
        "while.1 while (s32[8])": 30e-9, "fusion.2 fusion s32[8]": 20e-9,
        "fusion.3 fusion s32[8]": 50e-9, "copy.4 copy s32[8]": 20e-9})


def test_sweep_growth_sees_a_growing_backlog():
    from bench import sweep

    def records(latency):
        return [{"ok": True, "t_due": t, "t_done": t + latency(t)}
                for t in range(20)]

    assert sweep.recovery(records(lambda t: 2.0), 20, 4) == pytest.approx(1.0)
    # the latency comes back after the burst
    assert sweep.recovery(records(lambda t: 6.0 if 4 <= t < 8 else 2.0),
                          20, 4) == pytest.approx(1.0)
    # it stays where the burst put it
    assert sweep.recovery(records(lambda t: 2.0 if t < 4 else 4.0),
                          20, 4) > sweep.GROWTH
    tf = {"batch": 8, "rate_per_s": 2.0}
    assert sweep.burst_window(tf, 30.0) == (6.0, 10.0)
    stream = [(float(t), "bfs", t) for t in range(30)]
    _w, held = sweep.with_burst(lambda *a: ({}, stream))(tf, None, None, 1,
                                                          30.0)
    assert [t for t, _a, _r in held] == [
        10.0 if 6 <= t < 10 else float(t) for t in range(30)]

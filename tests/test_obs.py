"""repro.obs: tracing (zero-overhead no-op default, Chrome-trace export),
streaming metrics (log-bucket histogram vs an exact oracle), and
cost-model calibration (Spearman, cell/report assembly) — plus the
MergePlan accounting (`n_steps` / `wire_elements`) that span attrs and
graphs/cost_model.merge_wire_cost must both agree with, and the
traced ≡ untraced bit-identity of the instrumented phase pipeline
(the ISSUE-7 tentpole invariant), run on 8 subprocess devices."""
import json
import math
import os
import subprocess
import sys
import threading
import tracemalloc

import numpy as np
import pytest

from repro.obs import calibrate, metrics, trace

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# trace: the disabled path must be free
# ---------------------------------------------------------------------------

def test_disabled_span_is_the_shared_null_singleton():
    assert trace.active() is None and not trace.enabled()
    s1 = trace.span("anything", a=1)
    s2 = trace.span("else")
    assert s1 is s2 is trace.NULL_SPAN          # identity, not equality
    with s1 as s:
        assert s is trace.NULL_SPAN
        assert s.set(bytes=123) is trace.NULL_SPAN   # attrs swallowed


def test_disabled_span_retains_no_allocations():
    """The no-op path may allocate transiently (the kwargs dict) but must
    retain nothing — 10k disabled spans leave zero bytes attributed to
    the trace module."""
    for _ in range(100):                        # warm any caches first
        with trace.span("warm", a=1):
            pass
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(10_000):
        with trace.span("hot", a=1, b="x"):
            pass
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    retained = sum(st.size_diff for st in after.compare_to(before, "filename")
                   if st.traceback[0].filename == trace.__file__
                   and st.size_diff > 0)
    # allow interpreter-level noise (interned objects, free lists) but
    # nothing that scales with the call count: « 1 byte per call
    assert retained < 1024, f"{retained} bytes retained by 10k no-op spans"


def test_tracing_context_manager_installs_and_restores():
    assert trace.active() is None
    with trace.tracing() as t:
        assert trace.active() is t
        # nesting restores the *previous* tracer, not None
        with trace.tracing() as inner:
            assert trace.active() is inner
        assert trace.active() is t
    assert trace.active() is None
    # exception inside the block still uninstalls
    with pytest.raises(RuntimeError):
        with trace.tracing():
            raise RuntimeError("boom")
    assert trace.active() is None


# ---------------------------------------------------------------------------
# trace: ambient stitching attrs (Tracer.context)
# ---------------------------------------------------------------------------

def test_ambient_context_stitches_recorded_spans():
    """Spans recorded inside a context block inherit its attrs — live and
    retrospective alike; explicit attrs win; nesting merges inner-most
    first; spans outside the block are untouched."""
    t = trace.Tracer()
    with t.span("outside"):
        pass
    with t.context(window_id=3, request_ids="r1,r2"):
        with t.span("inside", phase="kernel"):
            pass
        t.add_span("retro", 1.0, 2.0)
        with t.context(window_id=4):
            with t.span("nested"):
                pass
        with t.span("explicit", window_id=9):
            pass
    with t.span("after"):
        pass

    by = {s.name: s for s in t.spans}
    assert "window_id" not in by["outside"].attrs
    assert by["inside"].attrs["window_id"] == 3
    assert by["inside"].attrs["request_ids"] == "r1,r2"
    assert by["inside"].attrs["phase"] == "kernel"
    assert by["retro"].attrs["window_id"] == 3      # add_span inherits too
    assert by["nested"].attrs["window_id"] == 4     # inner context wins
    assert by["nested"].attrs["request_ids"] == "r1,r2"   # outer still merged
    assert by["explicit"].attrs["window_id"] == 9   # explicit span attr wins
    assert "window_id" not in by["after"].attrs     # block closed cleanly


def test_ambient_context_is_thread_local():
    """Concurrent context blocks never cross-contaminate: each thread's
    spans carry only its own ambient attrs."""
    t = trace.Tracer()
    barrier = threading.Barrier(2)

    def worker(wid):
        with t.context(window_id=wid):
            barrier.wait()                  # both blocks open at once
            with t.span(f"w{wid}"):
                pass
            barrier.wait()

    threads = [threading.Thread(target=worker, args=(i,)) for i in (1, 2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    by = {s.name: s for s in t.spans}
    assert by["w1"].attrs["window_id"] == 1
    assert by["w2"].attrs["window_id"] == 2


def test_ambient_context_exception_safe_and_disabled_path_unchanged():
    t = trace.Tracer()
    with pytest.raises(RuntimeError):
        with t.context(a=1):
            raise RuntimeError("boom")
    assert t._ambient_attrs() is None       # stack popped on the way out
    with t.span("clean"):
        pass
    assert "a" not in t.by_name()["clean"][0].attrs
    # the disabled path is untouched by the ambient machinery: no tracer
    # installed still means the shared NULL_SPAN singleton
    assert trace.active() is None
    assert trace.span("x", a=1) is trace.NULL_SPAN


def _profiled_events(tmp_path, name):
    """The profiler-trace events called ``name`` under ``tmp_path``."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    return [e for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events if e.name == name]


def test_live_span_writes_the_profiler_trace(tmp_path):
    """With a profiler session and no tracer, a span is a TraceAnnotation
    whose attrs become stats on the host event; with a tracer installed
    too it lands in both; with neither it is the shared no-op again."""
    import jax

    assert trace.span("serve/flush", window_id=3) is trace.NULL_SPAN
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("serve/flush", window_id=3) as s:
            assert s is not trace.NULL_SPAN
        with trace.tracing() as t:
            with trace.span("serve/payload", algorithm="bfs", rows=8):
                pass
    finally:
        jax.profiler.stop_trace()
    assert trace.span("serve/flush", window_id=3) is trace.NULL_SPAN
    flush, = _profiled_events(tmp_path, "serve/flush")
    assert ("window_id", 3) in list(flush.stats)
    assert flush.end_ns >= flush.start_ns
    payload, = _profiled_events(tmp_path, "serve/payload")
    assert {("algorithm", "bfs"), ("rows", 8)} <= set(payload.stats)
    recorded, = t.filter("serve/payload")
    assert recorded.attrs == {"algorithm": "bfs", "rows": 8}


def test_span_set_reaches_the_profiler_trace(tmp_path):
    """An attribute known only inside the span (a bucket's loop trips)
    lands on the profiler's event beside those given at entry, and in
    the Tracer."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.tracing() as t:
            with trace.span("serve/bucket_compute", rows=8) as s:
                s.set(trips=417)
    finally:
        jax.profiler.stop_trace()
    event, = _profiled_events(tmp_path, "serve/bucket_compute")
    assert {("rows", 8), ("trips", 417)} <= set(event.stats)
    recorded, = t.filter("serve/bucket_compute")
    assert recorded.attrs == {"rows": 8, "trips": 417}


# ---------------------------------------------------------------------------
# trace: recording + export
# ---------------------------------------------------------------------------

def test_tracer_spans_queries_and_totals():
    t = trace.Tracer()
    with t.span("phase/kernel", phase="kernel", strategy="col") as s:
        s.set(bytes=64)
    with t.span("phase/load", phase="load", strategy="row"):
        pass
    t.add_span("serve/enqueue_wait", 1.0, 1.5, algorithm="bfs")
    assert len(t.spans) == 3
    assert set(t.by_name()) == {"phase/kernel", "phase/load",
                                "serve/enqueue_wait"}
    k = t.by_name()["phase/kernel"][0]
    assert k.attrs["bytes"] == 64 and k.duration >= 0
    assert t.total("serve/") == pytest.approx(0.5)
    assert t.total() >= 0.5
    assert [s.name for s in t.filter("phase/", strategy="col")] \
        == ["phase/kernel"]
    t.clear()
    assert t.spans == [] and t.total() == 0.0


def test_chrome_trace_export(tmp_path):
    t = trace.Tracer()
    t.add_span("phase/kernel", t.epoch + 0.002, t.epoch + 0.005,
               phase="kernel", devices=8, plan=("not", "primitive"))
    t.add_span("phase/load", t.epoch, t.epoch + 0.001, phase="load")
    path = tmp_path / "trace.json"
    assert t.export_chrome_trace(path) == 2
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert [e["name"] for e in events] == ["phase/load", "phase/kernel"]
    for e in events:
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] > 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    kern = events[1]
    assert kern["cat"] == "kernel" and kern["ts"] == pytest.approx(2000)
    assert kern["dur"] == pytest.approx(3000)
    # non-primitive attrs are stringified so the JSON always serializes
    assert kern["args"]["plan"] == str(("not", "primitive"))


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_and_registry_idempotency():
    reg = metrics.MetricsRegistry()
    assert reg.counter("served") is reg.counter("served")
    reg.counter("served").inc(); reg.counter("served").inc(2)
    g = reg.gauge("queue_depth")
    g.set(5.0); g.set(2.0)
    snap = reg.snapshot()
    assert snap["counters"] == {"served": 3}
    assert snap["gauges"]["queue_depth"] == \
        {"value": 2.0, "min": 2.0, "max": 5.0, "writes": 2}
    # unwritten gauges stay out of the snapshot
    reg.gauge("silent")
    assert "silent" not in reg.snapshot()["gauges"]
    # the snapshot is plain data: mutating it never touches the registry
    snap["counters"]["served"] = 999
    assert reg.snapshot()["counters"]["served"] == 3


def test_histogram_quantiles_match_exact_oracle():
    rng = np.random.default_rng(7)
    values = np.exp(rng.normal(-7.0, 1.5, size=5000))    # latency-shaped
    h = metrics.Histogram("lat_s")
    for v in values:
        h.observe(float(v))
    for q in (0.5, 0.9, 0.99):
        exact = metrics.percentile_exact([float(v) for v in values], q)
        est = h.quantile(q)
        # bucket growth 2^(1/4): the midpoint is within ~sqrt(growth) of
        # the exact nearest-rank value
        assert abs(math.log(est / exact)) <= math.log(h.growth), (q, est,
                                                                  exact)
    s = h.summary()
    assert s["count"] == 5000
    assert s["min"] == float(values.min()) and s["max"] == float(values.max())
    assert s["mean"] == pytest.approx(float(values.mean()))
    assert s["p50"] <= s["p90"] <= s["p99"]


def test_histogram_edge_cases():
    h = metrics.Histogram("h")
    assert h.quantile(0.5) == 0.0 and h.summary() == {"count": 0}
    h.observe(0.0); h.observe(-1.0)      # at/below `least`: bucket 0
    assert h.count == 2 and h.quantile(0.5) <= h.least
    one = metrics.Histogram("one")
    one.observe(0.25)
    # a single observation: every quantile is clamped into [lo, hi]
    assert one.quantile(0.5) == pytest.approx(0.25)
    with pytest.raises(ValueError):
        metrics.Histogram("bad", least=0.0)
    with pytest.raises(ValueError):
        metrics.Histogram("bad", growth=1.0)


def test_percentile_exact_nearest_rank():
    assert metrics.percentile_exact([], 0.5) == 0.0
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert metrics.percentile_exact(xs, 0.5) == 3.0
    assert metrics.percentile_exact(xs, 1.0) == 5.0
    assert metrics.percentile_exact(xs, 0.0) == 1.0


# ---------------------------------------------------------------------------
# calibrate
# ---------------------------------------------------------------------------

def test_spearman_basics():
    assert calibrate.spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert calibrate.spearman([1, 2, 3], [3, 2, 1]) == pytest.approx(-1.0)
    # monotone in rank even when wildly nonlinear in value
    assert calibrate.spearman([1, 2, 3, 4], [1, 100, 1e4, 1e8]) \
        == pytest.approx(1.0)
    # ties get average ranks: one swap among four with a tie stays high
    rho = calibrate.spearman([1.0, 2.0, 2.0, 3.0], [1.0, 2.5, 2.0, 3.0])
    assert 0.5 < rho < 1.0
    assert math.isnan(calibrate.spearman([1.0], [2.0]))        # < 2 points
    assert math.isnan(calibrate.spearman([1.0, 1.0], [1.0, 2.0]))  # constant
    with pytest.raises(ValueError):
        calibrate.spearman([1, 2], [1, 2, 3])


COST = {"load": 100.0, "kernel": 400.0, "retrieve": 30.0,
        "merge_wire": 50.0, "total": 580.0}


def test_predicted_phases_per_strategy():
    assert calibrate.predicted_phases(COST, "row") == \
        {"load": 100.0, "kernel": 400.0}
    assert calibrate.predicted_phases(COST, "col") == \
        {"kernel": 400.0, "retrieve_merge": 80.0}   # retrieve + merge_wire
    assert set(calibrate.predicted_phases(COST, "2d")) == \
        {"load", "kernel", "retrieve_merge"}


def test_phase_measurements_joins_on_attrs():
    t = trace.Tracer()
    t.add_span("phase/kernel", 0.0, 0.4, phase="kernel", strategy="col")
    t.add_span("phase/kernel", 1.0, 1.2, phase="kernel", strategy="col")
    t.add_span("phase/retrieve_merge", 0.4, 0.5, phase="retrieve_merge",
               strategy="col")
    t.add_span("phase/kernel", 2.0, 9.0, phase="kernel", strategy="row")
    t.add_span("serve/flush", 0.0, 9.9)          # not a phase span
    meas = calibrate.phase_measurements(t, strategy="col")
    assert meas["kernel"] == pytest.approx(0.6)
    assert meas["retrieve_merge"] == pytest.approx(0.1)
    assert "serve/flush" not in meas and len(meas) == 2


def test_calibration_cell_and_report():
    # measured agrees with predicted ordering: kernel > retrieve_merge
    cell = calibrate.calibration_cell(
        "rmat", "col", "tree", COST,
        {"kernel": 0.6, "retrieve_merge": 0.1}, measured_wall=0.75)
    assert cell["rho"] == pytest.approx(1.0) and cell["missing"] == []
    assert cell["predicted"]["retrieve_merge"] == pytest.approx(80.0)
    # a phase missing from the measurements drops out (and ρ needs >= 2)
    partial = calibrate.calibration_cell(
        "rmat", "2d", "staged2d", COST, {"kernel": 0.6})
    assert partial["missing"] == ["load", "retrieve_merge"]
    assert math.isnan(partial["rho"])
    # report: per-family cross-strategy ordering of totals vs walls
    other = calibrate.calibration_cell(
        "rmat", "row", "flat", dict(COST, total=900.0),
        {"load": 0.2, "kernel": 0.7}, measured_wall=0.95)
    report = calibrate.calibration_report([cell, other])
    o = report["ordering"]["rmat"]
    assert o["strategies"] == ["col", "row"]
    assert o["rho"] == pytest.approx(1.0)        # 580 < 900, 0.75 < 0.95
    text = calibrate.format_report(report)
    assert "rmat" in text and "+1.00" in text and "kernel" in text
    # disagreeing top phases get flagged
    bad = calibrate.calibration_cell(
        "road", "col", "flat", COST,
        {"kernel": 0.1, "retrieve_merge": 0.9}, measured_wall=1.0)
    assert "(!)" in calibrate.format_report(
        calibrate.calibration_report([bad]))


# ---------------------------------------------------------------------------
# MergePlan accounting vs the cost model (the span-attr source of truth)
# ---------------------------------------------------------------------------

def test_merge_plan_accounting_matches_cost_model():
    """`MergePlan.n_steps` / `wire_elements` (what phase spans report as
    `steps` / `bytes`) must agree with merge_wire_cost's unit-weight
    arithmetic — flat differs only by the documented HOST_HOP factor."""
    from repro.core.collectives import MERGE_FAMILIES, plan_merge
    from repro.graphs.cost_model import HOST_HOP, merge_wire_cost

    m = 4096.0
    for strategy, grid in (("col", (2, 4)), ("col", (1, 8)),
                           ("2d", (2, 4)), ("2d", (4, 2))):
        for topology in MERGE_FAMILIES:
            orders = ("rc", "cr") if topology == "staged2d" else ("rc",)
            for order in orders:
                plan = plan_merge(strategy, grid, topology, order=order)
                if plan is None:
                    continue
                cost = merge_wire_cost(strategy, grid, m, topology, order)
                assert cost["steps"] == plan.n_steps, (strategy, topology)
                wire = plan.wire_elements(m)
                if topology == "flat":
                    wire *= HOST_HOP
                assert cost["wire"] == pytest.approx(wire), \
                    (strategy, grid, topology, order)
    # row has no Merge phase at all
    assert plan_merge("row", (2, 4), "flat") is None


def test_plan_merge_span_records_plan_shape():
    from repro.core.collectives import plan_merge
    with trace.tracing() as t:
        plan = plan_merge("col", (2, 4), "tree")
    spans = t.filter("collective/plan_merge")
    assert len(spans) == 1
    s = spans[0]
    assert s.attrs["topology"] == "tree"
    assert s.attrs["axis_size"] == plan.axis_size
    assert s.attrs["steps"] == plan.n_steps


# ---------------------------------------------------------------------------
# pipeline_buckets spans (pure host-side: no devices needed)
# ---------------------------------------------------------------------------

def test_pipeline_buckets_traced_matches_untraced():
    items = list(range(7))
    issue = lambda i: i * 10                   # noqa: E731
    materialize = lambda i, h: h + i           # noqa: E731
    from repro.core.pipeline import pipeline_buckets
    expect = pipeline_buckets(issue, materialize, items, depth=2)
    with trace.tracing() as t:
        got = pipeline_buckets(issue, materialize, items, depth=2)
    assert got == expect == [i * 11 for i in items]
    issues = t.filter("pipeline/issue")
    mats = t.filter("pipeline/materialize")
    assert len(issues) == len(mats) == len(items)
    assert sorted(s.attrs["bucket"] for s in mats) == items


# ---------------------------------------------------------------------------
# the tentpole invariant: traced ≡ untraced on the real phase closures
# ---------------------------------------------------------------------------

WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.distributed import build_phase_fns
from repro.core.pipeline import iterate_phases
from repro.obs import calibrate, trace

rng = np.random.default_rng(0)
n = 192
dense = (rng.random((n, n)) < 0.06).astype(np.int32)
rows, cols = np.nonzero(dense)
vals = np.ones(len(rows), np.int32)
sr = BOOL_OR_AND
x = (rng.random(n) < 0.05).astype(np.int32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("dr", "dc"))

checked = 0
for strategy, grid, fmt, kern, topology in [
        ("row", (8, 1), "csr", "spmv", "flat"),
        ("col", (1, 8), "csc", "spmspv", "tree"),
        ("2d", (2, 4), "csc", "spmspv", "staged2d")]:
    pm = partition(rows, cols, vals, (n, n), grid, fmt, sr)
    xs = jnp.asarray(pm.plan.shard_input_vector(x, 0), sr.dtype)
    fns = build_phase_fns(mesh, pm, sr, strategy, kern, topology=topology)
    y0 = np.asarray(iterate_phases(fns, pm.parts, xs, 3))
    tracer = trace.Tracer()
    with trace.tracing(tracer):
        y1 = np.asarray(iterate_phases(fns, pm.parts, xs, 3))
    assert trace.active() is None
    np.testing.assert_array_equal(y0, y1, err_msg=strategy)

    meas = calibrate.phase_measurements(tracer, strategy=strategy)
    want = set(calibrate.PHASES_BY_STRATEGY[strategy])
    assert want <= set(meas), (strategy, sorted(meas))
    assert all(v > 0 for v in meas.values()), (strategy, meas)
    # span attrs carry the wire accounting the calibration joins on
    for s in tracer.filter("phase/retrieve_merge"):
        assert s.attrs["steps"] >= 1 and s.attrs["bytes"] > 0, s.attrs
    for s in tracer.filter("phase/", phase="load"):
        assert s.attrs["bytes"] > 0, s.attrs
    checked += 1
print("OBS_PHASES_OK", checked)
"""


@pytest.mark.slow
def test_traced_phases_bit_identical_8dev():
    """Installing a tracer must never change phase-pipeline results, and
    every phase the strategy runs must surface as a measured span with
    the attrs calibration joins on (ISSUE-7 acceptance)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", WORKER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "OBS_PHASES_OK 3" in res.stdout, res.stdout

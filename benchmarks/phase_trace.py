"""Traced phase pipeline + cost-model calibration (paper §5's
characterization methodology, run against our own cost model).

Per Table-2 family × Fig.-3 strategy this bench:

1. builds the per-phase closures (core.distributed.build_phase_fns) with
   the Merge topology the wire-cost model picks for that cell
   (estimate_phase_costs merge="auto" — the planner's pick is what runs);
2. iterates a BOOL_OR_AND frontier (values stay {0, 1}: int32-exact at
   any iteration count, so checksums are deterministic and the CI gate
   can diff them) through core.pipeline.iterate_phases — once untraced,
   once under an installed repro.obs tracer — and **asserts the two runs
   are bit-identical** (tracing moves host sync points, never values);
3. asserts the traced run's per-phase span sums cover its wall time
   within 10% (with a tracer installed every phase blocks inside its
   span — the paper's blocking-DMA schedule — so anything outside the
   spans is host loop overhead);
4. joins the measured spans against the cost row
   (obs.calibrate.calibration_cell) and prints the full predicted-vs-
   observed rank-correlation report, asserting the rmat × {col, 2d}
   cells positive — the skew-dominated cells where Kernel must rank top
   on both sides (the paper's central §5 observation);
5. exports every span as one Chrome-trace/Perfetto JSON artifact
   (``$PHASE_TRACE_OUT``, default ``phase-trace.json``; CI uploads it)
   and re-reads it to validate the traceEvents structure.

The rmat family here is larger than partition_balance's so the Kernel
phase dominates both columns by a margin, not a coin flip — rank
assertions on shared 2-core CI runners must not ride on sub-100µs
dispatch noise.
"""
from benchmarks import common  # noqa: F401  (must be first: device count)

import hashlib
import json
import os
import time

import numpy as np

from benchmarks.common import emit, make_dense_vector
from benchmarks.phases import STRATEGIES, prep, shard_x
from repro.core.distributed import build_phase_fns
from repro.core.pipeline import iterate_phases
from repro.core.semiring import BOOL_OR_AND
from repro.graphs import datasets
from repro.graphs.cost_model import estimate_phase_costs
from repro.obs import calibrate, trace
from repro.launch.mesh import make_mesh


def _graphs(quick: bool):
    s = 1 if quick else 3
    return [
        ("road", datasets.road_graph(1600 * s, 2.6, seed=0)),
        ("uniform", datasets.uniform_graph(1500 * s, 6000 * s, seed=0)),
        ("rmat", datasets.rmat_graph(4096 * s, 60000 * s, skew=0.6, seed=0)),
    ]


def run(quick: bool = False):
    mesh = make_mesh((2, 4), ("dr", "dc"))
    sr = BOOL_OR_AND
    n_iters = 4 if quick else 6
    cells = []
    export = trace.Tracer()

    for fam, g in _graphs(quick):
        for strategy, grid, fmt, kern in STRATEGIES:
            pm = prep(g, sr, grid, fmt)
            cost = estimate_phase_costs(pm.plan, strategy, kernel=kern,
                                        mesh_grid=(2, 4), merge="auto")
            fns = build_phase_fns(mesh, pm, sr, strategy, kern,
                                  topology=cost["merge"],
                                  merge_order=cost["merge_order"])
            x = np.asarray(make_dense_vector(g.n, 0.02, sr, seed=1))
            xs = shard_x(x, pm, sr)

            iterate_phases(fns, pm.parts, xs, n_iters)        # compile
            t0 = time.perf_counter()
            y_untraced = np.asarray(iterate_phases(fns, pm.parts, xs,
                                                   n_iters))
            untraced_s = time.perf_counter() - t0

            tracer = trace.Tracer()
            with trace.tracing(tracer):
                t0 = time.perf_counter()
                y_traced = np.asarray(iterate_phases(fns, pm.parts, xs,
                                                     n_iters))
                traced_s = time.perf_counter() - t0

            # tracing must never change answers
            np.testing.assert_array_equal(
                y_traced, y_untraced,
                err_msg=f"traced != untraced: {fam}/{strategy}")

            # span coverage: every phase blocks inside its span under the
            # tracer, so the sum must account for the wall within 10%
            span_sum = tracer.total("phase/")
            cov = span_sum / traced_s
            assert 0.9 <= cov <= 1.01, (
                f"{fam}/{strategy}: phase spans cover {cov:.1%} of the "
                f"traced wall ({span_sum * 1e3:.2f} of "
                f"{traced_s * 1e3:.2f} ms)")

            cell = calibrate.calibration_cell(
                fam, strategy, cost["merge"], cost,
                calibrate.phase_measurements(tracer, strategy=strategy),
                measured_wall=traced_s)
            cells.append(cell)
            export.epoch = min(export.epoch, tracer.epoch)
            export.spans.extend(tracer.spans)

            csum = hashlib.sha1(
                y_traced.astype(np.int64).tobytes()).hexdigest()[:12]
            emit("phase_trace", f"{fam}/{strategy}",
                 topology=cost["merge"], checksum=csum,
                 untraced_ms=untraced_s * 1e3, traced_ms=traced_s * 1e3,
                 span_cov_pct=cov * 100,
                 rho=cell["rho"] if cell["rho"] == cell["rho"] else 0.0)

    report = calibrate.calibration_report(cells)
    print(calibrate.format_report(report))
    for fam, o in report["ordering"].items():
        emit("phase_trace", f"{fam}/ordering", rho=o["rho"])

    # the skew-dominated cells: Kernel must rank top on both sides
    by_key = {(c["family"], c["strategy"]): c for c in cells}
    for strategy in ("col", "2d"):
        rho = by_key[("rmat", strategy)]["rho"]
        assert rho > 0, (
            f"rmat/{strategy}: predicted-vs-measured phase rank "
            f"correlation {rho} not positive — cost model disagrees with "
            f"the measured breakdown")

    # Chrome-trace artifact: write, then re-read and validate structure
    out_path = os.environ.get("PHASE_TRACE_OUT", "phase-trace.json")
    n_events = export.export_chrome_trace(out_path)
    doc = json.loads(open(out_path).read())
    events = doc["traceEvents"]
    assert len(events) == n_events and n_events > 0, (len(events), n_events)
    for e in events:
        assert e["ph"] == "X" and e["dur"] >= 0 and "name" in e \
            and "ts" in e, e
    emit("phase_trace", "artifact", events=n_events)
    print(f"phase_trace: wrote {n_events} spans to {out_path}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(quick=ap.parse_args().quick)

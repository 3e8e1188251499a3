"""Overlap efficiency of the pipelined phase engine (the paper's
non-blocking-DMA recommendation, core.pipeline).

For each Fig.-3 partitioning strategy and Table-2 family, an n-iteration
PageRank-style traversal loop (column-stochastic ⟨+,×⟩ SpMV) is run three
ways over the *same* per-phase closures
(core.distributed.build_phase_fns):

* ``phase_sum``  — the sequential per-phase accounting of
  benchmarks/phases.py: each phase timed in isolation with a blocking
  sync, summed over phases and iterations. This is the schedule UPMEM's
  blocking DMA enforces — the paper's measured baseline.
* ``blocking``   — wall time of the loop with a hard sync after every
  phase (core.pipeline.iterate_phases, depth=0).
* ``pipelined``  — wall time with phases dispatched asynchronously and up
  to ``depth`` iterations in flight (depth>=1), so Retrieve+Merge of
  iteration t overlaps the Load of t+1.

``overlap_eff = 1 - pipelined/phase_sum`` is the fraction of the
sequential phase-sum hidden by the non-blocking schedule. Results are
bit-identical across schedules (asserted in tests/test_distributed.py);
this module only reports time.
"""
from benchmarks import common  # noqa: F401  (pins device count first)

import time

import numpy as np

from benchmarks.common import emit, make_dense_vector, timeit
from benchmarks.phases import phase_times, prep, shard_x
from repro.core.distributed import build_phase_fns
from repro.core.pipeline import iterate_phases
from repro.core.semiring import PLUS_TIMES
from repro.graphs.datasets import generate
from repro.launch.mesh import make_mesh

# one family per Table-2 generator class: rmat / uniform / road
FAMILIES = ["face", "p2p-24", "r-TX"]
STRATEGIES = [("row", (8, 1), "csr"), ("col", (1, 8), "coo"),
              ("2d", (2, 4), "coo")]


def _wall(fn, iters: int = 5) -> float:
    """Min wall seconds of ``fn()`` over ``iters`` reps (fn blocks
    internally; min de-noises scheduler jitter on a shared host)."""
    fn()  # warmup (compilation of every phase closure)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts))


def run(quick: bool = False, depth: int = 4):
    sr = PLUS_TIMES
    mesh = make_mesh((2, 4), ("dr", "dc"))
    families = FAMILIES[:2] if quick else FAMILIES
    # Iteration count amortizes the per-phase sync cost the pipeline
    # removes; graph scales keep the loop latency-bound (the paper's
    # small-transfer regime, where blocking DMA hurts most).
    n_iters = 16 if quick else 32
    scale = {"face": 0.2, "p2p-24": 0.1, "r-TX": 0.004}
    wins = []
    for fam in families:
        g = generate(fam, scale=scale[fam] * (0.5 if quick else 1.0), seed=0)
        x = np.asarray(make_dense_vector(g.n, 1.0, sr, seed=1))
        for strategy, grid, fmt in STRATEGIES:
            pm = prep(g, sr, grid, fmt, normalize=True)
            xs = shard_x(x, pm, sr)
            # one closure set per cell: phase_times re-times the same
            # compiled fns the pipelined/blocking loops execute
            fns = build_phase_fns(mesh, pm, sr, strategy, "spmv")
            t = phase_times(mesh, pm, sr, strategy, "spmv", xs, timeit,
                            fns=fns)
            phase_sum = (t["load"] + t["kernel"] + t["retrieve_merge"]) \
                * n_iters
            t_blk = _wall(lambda: iterate_phases(fns, pm.parts, xs, n_iters,
                                                 depth=0))
            t_pip = _wall(lambda: iterate_phases(fns, pm.parts, xs, n_iters,
                                                 depth=depth))
            overlapped = t_pip < phase_sum
            wins.append((fam, strategy, overlapped))
            emit("pipeline_overlap", f"{fam}/{strategy}",
                 phase_sum_ms=phase_sum * 1e3, blocking_ms=t_blk * 1e3,
                 pipelined_ms=t_pip * 1e3,
                 overlap_eff=1.0 - t_pip / phase_sum,
                 speedup_vs_blocking=t_blk / t_pip,
                 pipelined_below_phase_sum=int(overlapped))
    hidden = sum(1 for *_k, ok in wins if ok)
    print(f"pipeline_overlap: pipelined wall below sequential phase-sum in "
          f"{hidden}/{len(wins)} (family, strategy) cells", flush=True)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--depth", type=int, default=4,
                    help="max in-flight iterations of the pipelined run")
    args = ap.parse_args()
    run(quick=args.quick, depth=args.depth)

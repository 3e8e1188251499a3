"""Per-phase (Load / Kernel / Retrieve+Merge) accounting for the
distributed engine — the paper's four-phase breakdown (Figs 2, 5, 6, 8).

The phase closures themselves live in ``repro.core.distributed
.build_phase_fns`` (the vocabulary's single definition point); this module
times them under the paper's *blocking* schedule — a hard sync after every
phase — which is exactly what UPMEM's blocking DMA enforces in hardware.
``benchmarks/pipeline_overlap.py`` measures the same closures under the
non-blocking schedule (core.pipeline) and reports the gap.

``run(quick=...)`` emits the per-phase timings as metric rows so the CI
artifact carries the Fig-2/5/6/8-style accounting (`python -m
benchmarks.run --json`); the fig* modules import the helpers below for
their own sweeps.
"""
from __future__ import annotations

from benchmarks import common  # noqa: F401  (pins device count first)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import build_phase_fns  # noqa: F401  (re-export)
from repro.core.partition import PartitionedMatrix, partition
from repro.core.semiring import Semiring
from repro.launch.mesh import make_mesh


def phase_times(mesh, pm, sr, strategy, kernel, xs, timeit,
                f_local: int | None = None, fns=None):
    """Measure Load / Kernel / Retrieve+Merge / e2e (seconds), each phase
    timed in isolation with a blocking sync (the paper's DMA schedule).
    Pass prebuilt ``fns`` (an undonated build_phase_fns dict) to reuse
    compiled closures across measurements — phases are re-timed against
    the same inputs, so donated buffers must NOT be enabled here."""
    if fns is None:
        fns = build_phase_fns(mesh, pm, sr, strategy, kernel, f_local=f_local)
    out = {}
    xf = None
    if fns["load"] is not None:
        out["load"] = timeit(fns["load"], pm.parts, xs)
        if fns["kernel"] is not None:
            xf = fns["load"](pm.parts, xs)
    else:
        out["load"] = 0.0
        xf = xs
    out["e2e"] = timeit(fns["e2e"], pm.parts, xs)
    if fns["kernel"] is not None:
        out["kernel"] = timeit(fns["kernel"], pm.parts, xs, xf)
        ys = fns["kernel"](pm.parts, xs, xf)
        if fns["retrieve_merge"] is not None:
            out["retrieve_merge"] = timeit(fns["retrieve_merge"], pm.parts, ys)
        else:
            out["retrieve_merge"] = 0.0
    else:
        out["retrieve_merge"] = 0.0
        out["kernel"] = max(out["e2e"] - out["load"], 0.0)
    return out


def prep(graph, sr, grid, fmt, weighted=False, normalize=False, seed=0,
         block=(16, 16), balance="rows"):
    """Partition a graph's transposed adjacency. The global shape is padded
    to a multiple of 64 so every grid x device-count combination divides.
    ``balance`` picks the PartitionPlan's cut mode (core.partition)."""
    from repro.graphs.engine import edge_values
    vals = edge_values(graph, sr, weighted, seed, normalize)
    rows, cols = graph.cols.astype(np.int32), graph.rows.astype(np.int32)
    n_pad = -(-graph.n // 64) * 64
    pm = partition(rows, cols, vals, (n_pad, n_pad), grid, fmt, sr,
                   block=block, balance=balance)
    return pm


def shard_x(x_np: np.ndarray, pm: PartitionedMatrix, sr: Semiring):
    """Global vector → the plan's canonical input layout (device block)."""
    fill = np.inf if sr.name == "min_plus" else 0
    xp = np.full(pm.plan.shape[1], fill, dtype=np.asarray(x_np).dtype)
    xp[: x_np.shape[0]] = x_np
    return jnp.asarray(pm.plan.shard_input_vector(xp, fill), sr.dtype)


STRATEGIES = [("row", (8, 1), "csr", "spmv"),
              ("col", (1, 8), "csc", "spmspv"),
              ("2d", (2, 4), "csc", "spmspv")]


def run(quick: bool = False):
    """Emit per-phase timing rows per Table-2 family x Fig-3 strategy x
    traversal semiring — the paper-figure accounting as --json metrics."""
    from benchmarks.common import emit, make_dense_vector, timeit
    from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
    from repro.graphs.datasets import generate

    mesh = make_mesh((2, 4), ("dr", "dc"))
    families = ["face"] if quick else ["face", "p2p-24"]
    algos = [("bfs", BOOL_OR_AND, 0.3), ("sssp", MIN_PLUS, 0.3),
             ("ppr", PLUS_TIMES, 1.0)]
    for fam in families:
        g = generate(fam, scale=0.1 if quick else 0.2, seed=0)
        for name, sr, dens in algos:
            x = np.asarray(make_dense_vector(g.n, dens, sr, seed=1))
            for strategy, grid, fmt, kern in STRATEGIES:
                pm = prep(g, sr, grid, fmt,
                          weighted=(sr.name == "min_plus"),
                          normalize=(sr.name == "plus_times"))
                t = phase_times(mesh, pm, sr, strategy, kern,
                                shard_x(x, pm, sr), timeit)
                emit("phases", f"{fam}/{name}/{strategy}",
                     load_ms=t["load"] * 1e3, kernel_ms=t["kernel"] * 1e3,
                     retrieve_merge_ms=t["retrieve_merge"] * 1e3,
                     e2e_ms=t["e2e"] * 1e3)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(quick=ap.parse_args().quick)

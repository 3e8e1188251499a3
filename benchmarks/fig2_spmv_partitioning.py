"""Fig 2: execution-time breakdown of 1D vs 2D SpMV partitioning.

Paper: SparseP's COO.nnz (1D row) vs DCOO (2D), 2048 DPUs, int32 — 1D pays
for broadcasting the dense input vector; 2D pays retrieve+merge instead.
Here: COO row-wise vs COO 2D over the 8-device CPU mesh, dense input vector.
"""
from benchmarks import common  # noqa: F401  (must be first: device count)

import numpy as np

from benchmarks.common import emit, make_dense_vector, timeit
from benchmarks.phases import phase_times, prep, shard_x
from repro.core.semiring import PLUS_TIMES
from repro.graphs.datasets import generate
from repro.launch.mesh import make_mesh


def run(quick: bool = False):
    mesh = make_mesh((2, 4), ("dr", "dc"))
    scale = 0.05 if quick else 0.15
    sr = PLUS_TIMES
    for ds in ["face", "A302"] if not quick else ["face"]:
        g = generate(ds, scale=scale, seed=0)
        x = np.asarray(make_dense_vector(g.n, 1.0, sr))
        base = None
        for case, grid, strategy in [("1D-row", (8, 1), "row"),
                                     ("2D", (2, 4), "2d")]:
            pm = prep(g, sr, grid, "coo")
            xs = shard_x(x, pm, sr)
            t = phase_times(mesh, pm, sr, strategy, "spmv", xs, timeit)
            if base is None:
                base = t["e2e"]
            emit("fig2", f"{ds}/{case}",
                 load_ms=t["load"] * 1e3, kernel_ms=t["kernel"] * 1e3,
                 retrieve_merge_ms=t["retrieve_merge"] * 1e3,
                 e2e_ms=t["e2e"] * 1e3, norm_to_1d=t["e2e"] / base)


if __name__ == "__main__":
    run()

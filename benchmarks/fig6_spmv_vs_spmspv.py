"""Fig 6: best SpMV vs best SpMSpV (CSC-2D) across input-vector densities
1/10/30/50% — SpMSpV's load-cost advantage shrinks as the vector densifies.
"""
from benchmarks import common  # noqa: F401

import numpy as np

from benchmarks.common import emit, make_dense_vector, timeit
from benchmarks.phases import phase_times, prep, shard_x
from repro.core.semiring import PLUS_TIMES
from repro.graphs.datasets import generate
from repro.launch.mesh import make_mesh


def run(quick: bool = False):
    mesh = make_mesh((2, 4), ("dr", "dc"))
    sr = PLUS_TIMES
    datasets = ["face", "A302"] if not quick else ["face"]
    for ds in datasets:
        g = generate(ds, scale=0.05 if ds == "A302" else 0.2, seed=0)
        pm_mv = prep(g, sr, (2, 4), "coo")      # paper's DCOO analogue
        pm_msv = prep(g, sr, (2, 4), "csc")     # CSC-2D
        for dens in [0.01, 0.10, 0.30, 0.50]:
            x = np.asarray(make_dense_vector(g.n, dens, sr, seed=7))
            t_mv = phase_times(mesh, pm_mv, sr, "2d", "spmv",
                               shard_x(x, pm_mv, sr), timeit)
            n_per = pm_msv.shape[1] // pm_msv.n_devices
            f_local = max(32, int(dens * n_per * 4) // 8 * 8)
            t_msv = phase_times(mesh, pm_msv, sr, "2d", "spmspv",
                                shard_x(x, pm_msv, sr), timeit,
                                f_local=f_local)
            emit("fig6", f"{ds}/d{int(dens*100)}",
                 spmv_ms=t_mv["e2e"] * 1e3, spmspv_ms=t_msv["e2e"] * 1e3,
                 spmspv_vs_spmv=t_msv["e2e"] / t_mv["e2e"],
                 spmv_load_ms=t_mv["load"] * 1e3,
                 spmspv_load_ms=t_msv["load"] * 1e3)


if __name__ == "__main__":
    run()

"""Fig 8: phase breakdown vs device count (paper: 512/1024/2048 DPUs;
here 2/4/8 CPU devices). Load+Retrieve grow with device count for the
traversal semirings while the kernel shrinks — PPR (plus-times) stays
kernel-dominated.
"""
from benchmarks import common  # noqa: F401

import numpy as np

from benchmarks.common import emit, make_dense_vector, timeit
from benchmarks.phases import phase_times, prep, shard_x
from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
from repro.graphs.datasets import generate
from repro.launch.mesh import make_mesh

ALGOS = [("bfs", BOOL_OR_AND, 0.3), ("sssp", MIN_PLUS, 0.3),
         ("ppr", PLUS_TIMES, 1.0)]


def run(quick: bool = False):
    g = generate("face", scale=0.3 if not quick else 0.15, seed=0)
    counts = [2, 4, 8] if not quick else [2, 8]
    base = {}
    for d in counts:
        grid = {2: (1, 2), 4: (2, 2), 8: (2, 4)}[d]
        mesh_axes = make_mesh(grid, ("dr", "dc"))
        for name, sr, dens in ALGOS:
            pm = prep(g, sr, grid, "csc",
                      weighted=(sr.name == "min_plus"),
                      normalize=(sr.name == "plus_times"))
            x = np.asarray(make_dense_vector(g.n, dens, sr, seed=1))
            t = phase_times(mesh_axes, pm, sr, "2d", "spmspv",
                            shard_x(x, pm, sr), timeit)
            key = name
            if key not in base:
                base[key] = t["e2e"]
            emit("fig8", f"{name}/D{d}",
                 load_ms=t["load"] * 1e3, kernel_ms=t["kernel"] * 1e3,
                 retrieve_merge_ms=t["retrieve_merge"] * 1e3,
                 e2e_ms=t["e2e"] * 1e3, norm_to_smallest=t["e2e"] / base[key])


if __name__ == "__main__":
    run()

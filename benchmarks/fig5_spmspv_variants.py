"""Fig 5: SpMSpV design space — COO / CSC-R / CSC-C / CSC-2D at input
densities 1%, 10%, 50% (+ the §6.1 CSR-is-worst exclusion check).

Paper: 2048 DPUs; CSC-2D usually best at >=10% density, CSC-C wins on
road-like graphs, CSR uniformly worst (2.8x-25x). Same relative claims
verified here on the 8-device mesh.
"""
from benchmarks import common  # noqa: F401

import numpy as np

from benchmarks.common import emit, make_dense_vector, timeit
from benchmarks.phases import phase_times, prep, shard_x
from repro.core.semiring import PLUS_TIMES
from repro.graphs.datasets import generate
from repro.launch.mesh import make_mesh

VARIANTS = [
    ("COO", (8, 1), "row", "coo"),
    ("CSC-R", (8, 1), "row", "csc"),
    ("CSC-C", (1, 8), "col", "csc"),
    ("CSC-2D", (2, 4), "2d", "csc"),
]
CSR_VARIANT = ("CSR-R", (8, 1), "row", "csr")


def run(quick: bool = False, include_csr: bool = True):
    mesh = make_mesh((2, 4), ("dr", "dc"))
    sr = PLUS_TIMES
    datasets = ["face", "r-TX", "g-18"] if not quick else ["face"]
    densities = [0.01, 0.10, 0.50]
    variants = VARIANTS + ([CSR_VARIANT] if include_csr else [])
    for ds in datasets:
        g = generate(ds, scale=0.05 if ds != "face" else 0.2, seed=0)
        pms = {name: prep(g, sr, grid, fmt)
               for name, grid, _s, fmt in variants}
        for dens in densities:
            x = np.asarray(make_dense_vector(g.n, dens, sr, seed=3))
            base = None
            for name, grid, strategy, fmt in variants:
                pm = pms[name]
                xs = shard_x(x, pm, sr)
                # compressed Load (the paper's SpMSpV transfer): frontier
                # capacity sized from the density bound with 4x headroom
                n_per = pm.shape[1] // pm.n_devices
                f_local = (max(32, int(dens * n_per * 4) // 8 * 8)
                           if strategy in ("row", "2d") else None)
                t = phase_times(mesh, pm, sr, strategy, "spmspv", xs, timeit,
                                f_local=f_local)
                if base is None:
                    base = t["e2e"]
                emit("fig5", f"{ds}/d{int(dens*100)}/{name}",
                     load_ms=t["load"] * 1e3, kernel_ms=t["kernel"] * 1e3,
                     retrieve_merge_ms=t["retrieve_merge"] * 1e3,
                     e2e_ms=t["e2e"] * 1e3, norm_to_coo=t["e2e"] / base)


if __name__ == "__main__":
    run()

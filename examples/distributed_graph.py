"""The paper's distributed scenario on an 8-device mesh: one semiring SpMSpV
across the three partitioning strategies, with the four-phase accounting
(Load / Kernel / Retrieve+Merge) and the compressed-frontier Load variant.

    PYTHONPATH=src:. python examples/distributed_graph.py
"""
import os

if "jax" not in __import__("sys").modules:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.core.distributed import make_distributed_matvec
from repro.core.semiring import PLUS_TIMES
from repro.graphs.datasets import generate
from repro.graphs.engine import edge_values
from repro.launch.mesh import make_mesh
from repro.core.partition import partition


def main():
    sr = PLUS_TIMES
    g = generate("face", scale=0.3, seed=0)
    n_pad = -(-g.n // 64) * 64
    vals = edge_values(g, sr, weighted=False)
    rows, cols = g.cols.astype(np.int32), g.rows.astype(np.int32)
    mesh = make_mesh((2, 4), ("dr", "dc"))
    print(f"graph n={g.n} nnz={g.nnz}; mesh 2x4 (8 devices)")

    rng = np.random.default_rng(0)
    x = np.where(rng.random(n_pad) < 0.05, rng.random(n_pad), 0.0
                 ).astype(np.float32)
    oracle = None

    for name, grid, strategy, fmt in [("row/CSC-R", (8, 1), "row", "csc"),
                                      ("col/CSC-C", (1, 8), "col", "csc"),
                                      ("2d/CSC-2D", (2, 4), "2d", "csc")]:
        pm = partition(rows, cols, vals, (n_pad, n_pad), grid, fmt, sr)
        xs = jax.numpy.asarray(x.reshape(8, -1), sr.dtype)
        fn = jax.jit(make_distributed_matvec(mesh, pm, sr, strategy,
                                             kernel="spmspv"))
        y = np.asarray(fn(pm.parts, xs)).reshape(-1)[: g.n]
        if oracle is None:
            oracle = y
        err = np.abs(y - oracle).max()
        nnz_out = int((y != 0).sum())
        print(f"  {name:10s}: out nnz={nnz_out:6d}  max dev from row-wise={err:.2e}")

    # compressed-frontier Load (the paper's SpMSpV transfer saving): wire
    # bytes per device drop from n_per*(D-1) to 2*f_local*(D-1)
    pm = partition(rows, cols, vals, (n_pad, n_pad), (8, 1), "csc", sr)
    n_per = n_pad // 8
    f_local = max(64, int(0.05 * n_per * 4) // 8 * 8)
    fn_c = jax.jit(make_distributed_matvec(mesh, pm, sr, "row",
                                           kernel="spmspv", f_local=f_local))
    xs = jax.numpy.asarray(x.reshape(8, -1), sr.dtype)
    y = np.asarray(fn_c(pm.parts, xs)).reshape(-1)[: g.n]
    print(f"  compressed-Load row: matches={np.allclose(y, oracle)}  "
          f"Load bytes/device {n_per*7*4} -> {2*f_local*7*4} "
          f"({n_per/(2*f_local):.1f}x smaller)")


if __name__ == "__main__":
    main()

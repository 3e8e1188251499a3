"""Table 4: application execution time + compute utilization vs a classical
CPU baseline.

The paper compares UPMEM against GridGraph (CPU) / cuGraph (GPU). Without
that hardware, the roles map as: classical sequential numpy references =
the CPU baseline; the jitted ALPHA-PIM adaptive engine = the accelerated
system. Compute utilization = achieved useful semiring-op rate / measured
dense-matmul peak of this container — the paper's metric, same machine.
"""
from benchmarks import common  # noqa: F401

import jax

from benchmarks.common import bench_vs_reference, emit, peak_flops_cpu
from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, MIN_TIMES, PLUS_TIMES
from repro.graphs import (
    bfs, bfs_reference, cc_reference, connected_components, pagerank,
    pagerank_reference, ppr, ppr_reference, sssp, sssp_reference,
)
from repro.graphs.cost_model import trained_stump
from repro.graphs.datasets import generate, largest_component_source
from repro.graphs.engine import build_engine, edge_values


def useful_ops(g, res) -> float:
    """2*nnz per effective full matvec, each trip weighted by its frontier
    density: the frontier nonzeros read over all trips, over n."""
    return max(2.0 * g.nnz * int(res.consumed) / g.n, 2.0 * g.nnz)


def _bench(case: str, engine_fn, ref_fn, ops_fn, peak: float) -> None:
    bench_vs_reference("table4", case, engine_fn, ref_fn, ops_fn, peak)


def run(quick: bool = False):
    stump = trained_stump()
    peak = peak_flops_cpu(512 if quick else 1024)
    emit("table4", "peak", gflops=peak / 1e9)
    datasets = (["A302", "as00", "s-S11", "p2p-24", "e-En", "face"]
                if not quick else ["face", "as00"])
    for ds in datasets:
        g = generate(ds, scale=0.05 if ds in ("A302", "s-S11") else 0.25,
                     seed=0)
        src = largest_component_source(g)

        def whole_graph_ops(res):
            return 2.0 * g.nnz * int(res.iterations)

        # BFS
        eng = build_engine(g, BOOL_OR_AND, stump)
        _bench(f"{ds}/bfs", jax.jit(lambda: bfs(eng, src, policy="adaptive")),
               lambda: bfs_reference(g.rows, g.cols, g.n, src),
               lambda res: useful_ops(g, res), peak)

        # SSSP
        eng = build_engine(g, MIN_PLUS, stump, weighted=True, seed=5)
        w = edge_values(g, MIN_PLUS, weighted=True, seed=5)
        _bench(f"{ds}/sssp", jax.jit(lambda: sssp(eng, src, policy="adaptive")),
               lambda: sssp_reference(g.rows, g.cols, w, g.n, src),
               lambda res: useful_ops(g, res), peak)

        # PPR
        eng = build_engine(g, PLUS_TIMES, stump, normalize=True)
        _bench(f"{ds}/ppr", jax.jit(lambda: ppr(eng, src, policy="adaptive")),
               lambda: ppr_reference(g.rows, g.cols, g.n, src),
               lambda res: useful_ops(g, res), peak)

        # Full PageRank (whole-graph: dense from step 0, SpMV every round)
        _bench(f"{ds}/pagerank", jax.jit(lambda: pagerank(eng)),
               lambda: pagerank_reference(g.rows, g.cols, g.n),
               whole_graph_ops, peak)

        # Connected components (whole-graph ⟨min,×⟩ label flooding)
        eng = build_engine(g, MIN_TIMES, stump)
        _bench(f"{ds}/cc", jax.jit(lambda: connected_components(eng)),
               lambda: cc_reference(g.rows, g.cols, g.n),
               whole_graph_ops, peak)


if __name__ == "__main__":
    run()

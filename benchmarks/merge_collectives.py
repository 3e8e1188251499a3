"""Merge-collective benchmark: bytes-on-wire + wall time per Table-2
family × Fig.-3 strategy × core.collectives topology (paper §7's
"direct interconnection networks among PIM cores" recommendation).

Per (family, strategy, topology) row: the wire-cost model's **modeled
bytes each device puts on the interconnect** for the Merge phase
(graphs.cost_model.merge_wire_cost — flat's host bounce crosses the
narrow link twice per element, direct ring/tree/staged-2D links once),
the collective's latency step count, the distributed SpMV wall time,
and a **result checksum**.  Edge weights and inputs are small integers,
so float32 ⊕-accumulation is exact in ANY order and every topology is
bit-identical to the flat baseline and to the unpartitioned reference —
the checksum rows feed the CI bench-regression gate
(tools/compare_bench.py) like every other benchmark.

Asserted here (and thereby in the CI bench smoke):
* ring, tree, and staged-2D results are bit-identical to the flat merge
  on every family (integer checksums);
* every direct topology's modeled bytes-on-wire is strictly lower than
  the flat merge's, for both the col and 2d strategies, on every family;
* the auto pick (graphs.cost_model.choose_merge — the same pricing
  ``strategy="auto"`` rides) never scores worse than flat.

Row names: ``{family}/{strategy}/{topology}`` (+ ``staged2d:cr`` for the
transpose exchange order on col, and ``{family}/{strategy}/auto``).
"""
from benchmarks import common  # noqa: F401  (must be first: device count)

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.collectives import MERGE_FAMILIES
from repro.core.distributed import make_distributed_spmv
from repro.core.partition import partition
from repro.core.semiring import PLUS_TIMES
from repro.graphs import datasets
from repro.graphs.cost_model import (
    choose_merge, merge_wire_cost, strategy_grid,
)
from repro.launch.mesh import make_mesh

MESH_GRID = (2, 4)
ELEM_BYTES = 4                      # float32 payloads


def _graphs(quick: bool):
    s = 1 if quick else 3
    return [
        ("road", datasets.road_graph(1600 * s, 2.6, seed=0)),
        ("uniform", datasets.uniform_graph(1500 * s, 6000 * s, seed=0)),
        ("rmat", datasets.rmat_graph(2048 * s, 16000 * s, skew=0.6, seed=0)),
    ]


def run(quick: bool = False):
    mesh = make_mesh(MESH_GRID, ("dr", "dc"))
    sr = PLUS_TIMES
    for fam, g in _graphs(quick):
        rows = g.cols.astype(np.int64)    # transposed, like the engines
        cols = g.rows.astype(np.int64)
        n_pad = -(-g.n // 64) * 64
        rng = np.random.default_rng(7)
        vals = rng.integers(1, 9, rows.shape[0]).astype(np.float32)
        x = rng.integers(0, 9, n_pad).astype(np.float32)
        ref = np.zeros(n_pad, np.float32)
        np.add.at(ref, rows, vals * x[cols])    # integer-exact reference
        for strategy in ("col", "2d"):
            grid = strategy_grid(strategy, 8, MESH_GRID)
            pm = partition(rows, cols, vals, (n_pad, n_pad), grid,
                           "csr", sr, balance="nnz")
            m_loc = pm.plan.local_shape[0]
            m_merge = float(n_pad if strategy == "col" else m_loc)
            cases = [(t, "rc") for t in MERGE_FAMILIES]
            if strategy == "col":
                cases.append(("staged2d", "cr"))
            wire = {}
            checksums = {}
            for topology, order in cases:
                fn = jax.jit(make_distributed_spmv(
                    mesh, pm, sr, strategy,
                    topology=topology, merge_order=order))
                xs = jnp.asarray(pm.plan.shard_input_vector(x, 0.0),
                                 sr.dtype)
                y = pm.plan.unshard_output_vector(
                    np.asarray(jax.block_until_ready(fn(pm.parts, xs))))
                np.testing.assert_array_equal(
                    y, ref, err_msg=f"{fam}/{strategy}/{topology}")
                t = timeit(fn, pm.parts, xs, iters=3 if quick else 5,
                           warmup=1)
                mc = merge_wire_cost(strategy, MESH_GRID, m_merge,
                                     topology, order)
                name = topology if order == "rc" else f"{topology}:{order}"
                wire[name] = mc["wire"]
                csum = hashlib.sha1(
                    y.astype(np.int64).tobytes()).hexdigest()[:12]
                checksums[name] = csum
                emit("merge_collectives", f"{fam}/{strategy}/{name}",
                     wire_bytes=mc["wire"] * ELEM_BYTES,
                     merge_steps=mc["steps"], wall_ms=t * 1e3,
                     checksum=csum)
            # bit-identical: every topology reproduces the flat merge
            assert len(set(checksums.values())) == 1, (fam, strategy,
                                                       checksums)
            # the headline claim: direct links strictly beat the host
            # bounce on modeled bytes-on-wire, every family, col AND 2d
            for name, w in wire.items():
                if name != "flat":
                    assert w < wire["flat"], (fam, strategy, name, wire)
            topo, order, cost = choose_merge(strategy, MESH_GRID, m_merge)
            flat = merge_wire_cost(strategy, MESH_GRID, m_merge, "flat")
            assert cost["score"] <= flat["score"], (fam, strategy, cost)
            emit("merge_collectives", f"{fam}/{strategy}/auto",
                 chosen=topo if order == "rc" else f"{topo}:{order}",
                 wire_bytes=cost["wire"] * ELEM_BYTES,
                 merge_steps=cost["steps"])


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(quick=ap.parse_args().quick)

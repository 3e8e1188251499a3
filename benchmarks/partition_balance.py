"""Partition-planner benchmark: load imbalance + wall time per Table-2
family × Fig.-3 strategy × balance mode, plus the cost-model planner's
auto pick (paper §4.1.1; PrIM's idle-core finding).

Per (family, strategy, balance) row: the plan's **nnz imbalance factor**
(max per-device nnz / ideal equal share — the metric the assertions pin;
wall time is reported but never asserted, runners are 2-core), the
distributed SpMV wall time, and a **result checksum**.  Edge weights and
inputs are small integers, so float32 accumulation is exact in any order
and every partitioned result is bit-identical to the unpartitioned
reference — the checksum is deterministic and the CI bench-regression
gate (tools/compare_bench.py) diffs it against benchmarks/baseline.json.

Asserted here (and thereby in the CI bench smoke):
* balance="nnz" imbalance ≤ 1.15 on the rmat family for every strategy,
  while the equal-count row split exceeds 2 — the planner balances real
  work, not row counts;
* the auto choice's imbalance is never worse than the worst fixed
  strategy on any family.
"""
from benchmarks import common  # noqa: F401  (must be first: device count)

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.distributed import make_distributed_spmv
from repro.core.partition import BALANCES, partition
from repro.core.semiring import PLUS_TIMES
from repro.graphs import datasets
from repro.graphs.cost_model import STRATEGIES, choose_partition, strategy_grid
from repro.launch.mesh import make_mesh


def _graphs(quick: bool):
    s = 1 if quick else 3
    return [
        ("road", datasets.road_graph(1600 * s, 2.6, seed=0)),
        ("uniform", datasets.uniform_graph(1500 * s, 6000 * s, seed=0)),
        ("rmat", datasets.rmat_graph(2048 * s, 16000 * s, skew=0.6, seed=0)),
    ]


def run(quick: bool = False):
    mesh = make_mesh((2, 4), ("dr", "dc"))
    sr = PLUS_TIMES
    imb: dict = {}
    for fam, g in _graphs(quick):
        rows = g.cols.astype(np.int64)    # transposed, like the engines
        cols = g.rows.astype(np.int64)
        n_pad = -(-g.n // 64) * 64
        rng = np.random.default_rng(7)
        vals = rng.integers(1, 9, rows.shape[0]).astype(np.float32)
        x = rng.integers(0, 9, n_pad).astype(np.float32)
        ref = np.zeros(n_pad, np.float32)
        np.add.at(ref, rows, vals * x[cols])    # integer-exact reference
        for strategy in STRATEGIES:
            grid = strategy_grid(strategy, 8, (2, 4))
            for balance in BALANCES:
                pm = partition(rows, cols, vals, (n_pad, n_pad), grid,
                               "csr", sr, balance=balance)
                fn = jax.jit(make_distributed_spmv(mesh, pm, sr, strategy))
                xs = jnp.asarray(pm.plan.shard_input_vector(x, 0.0), sr.dtype)
                y = pm.plan.unshard_output_vector(
                    np.asarray(jax.block_until_ready(fn(pm.parts, xs))))
                np.testing.assert_array_equal(
                    y, ref, err_msg=f"{fam}/{strategy}/{balance}")
                t = timeit(fn, pm.parts, xs, iters=3 if quick else 5,
                           warmup=1)
                factor = pm.plan.imbalance()
                imb[(fam, strategy, balance)] = factor
                csum = hashlib.sha1(
                    y.astype(np.int64).tobytes()).hexdigest()[:12]
                emit("partition_balance", f"{fam}/{strategy}/{balance}",
                     imbalance=factor, nnz_max=max(pm.plan.tile_nnz),
                     wall_ms=t * 1e3, checksum=csum)
        choice = choose_partition(rows, cols, (n_pad, n_pad),
                                  n_devices=8, grid2d=(2, 4))
        auto_imb = choice.plan.imbalance()
        worst_fixed = max(imb[(fam, s, b)]
                          for s in STRATEGIES for b in BALANCES)
        emit("partition_balance", f"{fam}/auto",
             chosen=f"{choice.strategy}:{choice.balance}",
             imbalance=auto_imb)
        assert auto_imb <= worst_fixed + 1e-9, (
            f"auto pick ({auto_imb:.3f}) worse than worst fixed "
            f"({worst_fixed:.3f}) on {fam}")

    # The headline claim: nnz balancing fixes the skewed family the
    # equal-count split leaves idle (asserted on imbalance, never wall).
    assert imb[("rmat", "row", "rows")] > 2.0, imb[("rmat", "row", "rows")]
    for strategy in STRATEGIES:
        assert imb[("rmat", strategy, "nnz")] <= 1.15, (
            strategy, imb[("rmat", strategy, "nnz")])


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(quick=ap.parse_args().quick)

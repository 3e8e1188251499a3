"""Multi-query throughput: batched multi-source traversals vs the
per-source loop (the tentpole metric for the "many users, one graph"
regime — ISSUE 1 acceptance: >= 3x queries/sec at B=8 on 8 host devices).

Sequential baseline: one jitted single-source traversal (source traced, so
it compiles once), called B times. Batched: one jitted multi-source call.
Both run the same adaptive policy; batched rows are element-equal to the
sequential results (tests/test_multi_query.py).

The batched block runs UNsharded by default: B-lane kernels vectorize
inside one device, and on forced-host-platform CPU "devices" (threads over
one memory system) row-sharding the block just adds per-iteration
synchronization — measured slower. ``--shard`` row-shards the block over
the visible devices for mesh-path measurements on real accelerators.

    PYTHONPATH=src:. python -m benchmarks.multi_query [--batch 8] [--quick]
"""
from benchmarks import common  # noqa: F401  (pins device count first)

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
from repro.graphs import bfs, ppr, sssp
from repro.graphs.cost_model import trained_stump
from repro.graphs.datasets import generate
from repro.graphs.engine import build_engine
from repro.graphs.multi import make_bfs_multi, make_ppr_multi, make_sssp_multi
from repro.launch.mesh import make_mesh


def _mesh():
    n_dev = jax.device_count()
    if n_dev <= 1:
        return None
    return make_mesh((n_dev,), ("batch",))


def _engines(g, stump):
    return {
        "bfs": build_engine(g, BOOL_OR_AND, stump),
        "sssp": build_engine(g, MIN_PLUS, stump, weighted=True, seed=5),
        "ppr": build_engine(g, PLUS_TIMES, stump, normalize=True),
    }


def _sequential_fn(alg, eng, max_iters):
    single = {"bfs": bfs, "sssp": sssp, "ppr": ppr}[alg]
    kw = {"max_iters": max_iters} if alg != "ppr" else {}
    return jax.jit(functools.partial(single, eng, policy="adaptive", **kw))


def _batched_fn(alg, eng, batch, max_iters, mesh):
    make = {"bfs": make_bfs_multi, "sssp": make_sssp_multi,
            "ppr": make_ppr_multi}[alg]
    kw = {"max_iters": max_iters} if alg != "ppr" else {}
    return make(eng, batch, policy="adaptive", mesh=mesh,
                axis_name="batch", **kw)


def bench_case(alg, eng, sources, max_iters, mesh, iters=3):
    b = len(sources)
    seq = _sequential_fn(alg, eng, max_iters)

    def run_seq():
        return [seq(s) for s in sources]

    t_seq = timeit(run_seq, iters=iters, warmup=1)

    batched = _batched_fn(alg, eng, b, max_iters, mesh)
    src = jnp.asarray(np.asarray(sources), jnp.int32)
    t_bat = timeit(batched, src, iters=iters, warmup=1)

    qps_seq = b / t_seq
    qps_bat = b / t_bat
    return qps_seq, qps_bat, qps_bat / qps_seq


def run(quick: bool = False, batch: int = 8, shard: bool = False):
    stump = trained_stump()
    mesh = _mesh() if shard else None
    n_dev = jax.device_count()
    rng = np.random.default_rng(0)
    datasets = [("face", 0.5), ("p2p-24", 0.25)] if not quick \
        else [("face", 0.25)]
    speedups = []
    for ds, scale in datasets:
        g = generate(ds, scale=scale, seed=0)
        engines = _engines(g, stump)
        sources = [int(s) for s in rng.integers(0, g.n, batch)]
        for alg in ("bfs", "sssp", "ppr"):
            qps_seq, qps_bat, speedup = bench_case(
                alg, engines[alg], sources, max_iters=64, mesh=mesh)
            speedups.append(speedup)
            emit("multi_query", f"{ds}/{alg}",
                 n=g.n, nnz=g.nnz, batch=batch, devices=n_dev,
                 qps_sequential=qps_seq, qps_batched=qps_bat,
                 speedup=speedup)
    geo = float(np.exp(np.mean(np.log(np.maximum(speedups, 1e-9)))))
    emit("multi_query", "geomean", batch=batch, devices=n_dev, speedup=geo)
    return geo


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--shard", action="store_true",
                    help="row-shard the query block over the visible devices")
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="exit nonzero unless the geomean speedup clears this")
    args = ap.parse_args()
    geo = run(quick=args.quick, batch=args.batch, shard=args.shard)
    if args.min_speedup is not None and geo < args.min_speedup:
        raise SystemExit(
            f"geomean speedup {geo:.2f}x < required {args.min_speedup}x")


if __name__ == "__main__":
    main()

"""Multi-query graph serving: many users, one graph.

A GraphQueryServer batches (algorithm, source) requests, dedupes repeated
sources, serves hot queries from an LRU cache, and drains the rest through
the batched multi-source traversal engine — row-sharding each [B, n]
frontier block over the visible devices.

    PYTHONPATH=src:. python examples/multi_query_serving.py
"""
import os

if "jax" not in __import__("sys").modules:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.graphs.datasets import generate
from repro.launch.mesh import make_mesh
from repro.serve.graph_engine import GraphQueryServer


def main():
    g = generate("face", scale=0.5, seed=0)
    n_dev = jax.device_count()
    mesh = make_mesh((n_dev,), ("batch",)) if n_dev > 1 else None
    srv = GraphQueryServer(g, batch_size=8, cache_capacity=256, mesh=mesh)
    print(f"graph n={g.n} nnz={g.nnz}; {n_dev} devices; batch=8")

    # a burst of mixed traffic with repeats (think: popular profile pages)
    rng = np.random.default_rng(7)
    hot = [int(s) for s in rng.integers(0, g.n, 4)]
    for _ in range(3):
        for s in hot:
            srv.submit("bfs", s)
            srv.submit("ppr", s)
    for s in rng.integers(0, g.n, 8):
        srv.submit("sssp", int(s))

    done = srv.flush()
    stats = srv.stats()
    print(f"flush 1: {len(done)} queries -> {stats['batches']} engine "
          f"batches (deduped {stats['deduped']})")

    # the second wave of the same hot sources never touches the engine
    for s in hot:
        srv.submit("bfs", s)
    done = srv.flush()
    hits = sum(r.cached for r in done)
    print(f"flush 2: {len(done)} queries, {hits} served from LRU cache")

    r = done[0]
    reached = int((r.result["levels"] >= 0).sum())
    print(f"sample bfs(source={r.source}): reached {reached}/{g.n} vertices "
          f"in {r.result['iterations']} levels")

    # live mutation: stream an edge batch in; only affected entries drop
    from repro.core.delta import EdgeDelta
    ins = rng.integers(0, g.n, (8, 2))
    report = srv.mutate(EdgeDelta(insert_rows=ins[:, 0],
                                  insert_cols=ins[:, 1]))
    print(f"mutate -> v{report['version']}: +{report['inserted']} edges, "
          f"cache retained {report['retained']} / "
          f"invalidated {report['invalidated']}")
    print("stats:", srv.stats())


if __name__ == "__main__":
    main()

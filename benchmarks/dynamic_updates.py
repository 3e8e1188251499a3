"""Streaming-update benchmark: delta apply throughput + incremental vs
cold recompute per Table-2 family (graphs/dynamic.py over core/delta.py).

Per family × delta kind (``grow`` = insert-only batch, ``churn`` = mixed
insert+delete batch), rows report for BFS / SSSP the **element traffic**
(frontier elements the kernel consumed, the paper's Load-phase currency —
incremental includes the shared reachability repair pass) and wall time;
for CC / PageRank the **iteration counts** (dense whole-vertex rounds, so
iterations ∝ traffic). Wall numbers are artifact data only (2-core CI
runners); every assertion is on deterministic quantities:

* incremental results are **element-exact** vs cold recompute on every
  delta batch, for BFS, SSSP and CC (the ISSUE-5 acceptance bar);
* on ``grow`` batches incremental element traffic < cold on every family
  (road / uniform / rmat), and incremental CC iterations ≤ cold;
* warm-restart PageRank converges in fewer iterations than cold on the
  regular families (road / uniform; rmat hub perturbations can favour the
  uniform start, so its row is reported, not asserted);
* the query server's ``mutate()`` retains ≥ 1 cache entry across the
  delta while invalidating the affected ones (proved via ``stats()``).

Cold-result checksums are integer-exact (levels / labels are ints; SSSP
distances are sums of content-keyed integer weights, exact in f32) and
gate in CI via tools/compare_bench.py against benchmarks/baseline.json.
"""
from benchmarks import common  # noqa: F401  (must be first: device count)

import hashlib
import time

import numpy as np

from benchmarks.common import emit, timeit
from repro.core.delta import EdgeDelta, apply_edge_delta, canonicalize
from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, MIN_TIMES, PLUS_TIMES
from repro.graphs import datasets
from repro.graphs.analytics import connected_components
from repro.graphs.dynamic import (
    DynamicGraph, bfs_incremental, cc_incremental, pagerank_warm,
    plan_repair, sssp_incremental, traffic_of,
)
from repro.graphs.engine import build_engine
from repro.graphs.multi import bfs_multi, sssp_multi
from repro.graphs.ppr import pagerank
from repro.serve.graph_engine import GraphQueryServer

PR_ITERS = 200


def _graphs(quick: bool):
    s = 1 if quick else 3
    return [
        ("road", datasets.road_graph(1600 * s, 2.6, seed=0)),
        ("uniform", datasets.uniform_graph(1500 * s, 6000 * s, seed=0)),
        ("rmat", datasets.rmat_graph(2048 * s, 16000 * s, skew=0.6, seed=0)),
    ]


def _local_inserts(g, k: int, rng):
    """Triangle-closing insert candidates: for k random edges (u, v), a
    random neighbour w of v gives a new (u, w) edge. Streamed graph
    updates are overwhelmingly local (new links attach near existing
    ones); locality is also what keeps the answer delta — and with it the
    incremental ripple — small. Uniformly random endpoints would instead
    act as small-world shortcuts on the road lattice and legitimately
    shrink most shortest paths, making cold recompute the honest
    choice."""
    order = np.argsort(g.rows, kind="stable")
    sorted_cols = g.cols[order]
    ptr = np.searchsorted(g.rows[order], np.arange(g.n + 1))
    e = rng.choice(g.nnz, k, replace=True)
    u, v = g.rows[e], g.cols[e]
    deg = ptr[v + 1] - ptr[v]           # ≥ 1: v has out-edges (symmetric)
    off = (rng.random(k) * deg).astype(np.int64)
    w = sorted_cols[ptr[v] + off]
    return u, w                          # self loops/duplicates: no-ops


def _deltas(g):
    """One insert-only and one mixed batch per family, sized ~1% of nnz."""
    rng = np.random.default_rng(11)
    k = max(8, g.nnz // 100)
    gu, gw = _local_inserts(g, k, rng)
    grow = EdgeDelta(insert_rows=gu, insert_cols=gw)
    cu, cw = _local_inserts(g, k, rng)
    drop = rng.choice(g.nnz, max(4, k // 2), replace=False)
    churn = EdgeDelta(cu, cw, g.rows[drop], g.cols[drop])
    return [("grow", grow), ("churn", churn)]


def _csum(arr: np.ndarray) -> str:
    a = np.asarray(arr, np.float64)
    ints = np.where(np.isfinite(a), a, -1.0).astype(np.int64)
    return hashlib.sha1(ints.tobytes()).hexdigest()[:12]


def _apply_throughput(fam: str, g, delta: EdgeDelta, reps: int):
    """Delta apply wall time (pure host set algebra) — min over reps."""
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        apply_edge_delta(g.rows, g.cols, g.n, delta)
        best = min(best, time.perf_counter() - t0)
    d = canonicalize(delta, g.n)
    edges = d.n_inserts + d.n_deletes
    emit("dynamic_updates", f"{fam}/apply", wall_ms=best * 1e3,
         edges=edges, edges_per_s=edges / best)


def run(quick: bool = False):
    reps = 3 if quick else 5
    for fam, g0 in _graphs(quick):
        rng = np.random.default_rng(5)
        sources = [int(s) for s in rng.integers(0, g0.n, 4)]
        # previous-epoch answers (the state incremental recompute resumes)
        e0_bool = build_engine(g0, BOOL_OR_AND)
        e0_w = build_engine(g0, MIN_PLUS, weighted=True, seed=5,
                            content_keyed=True)
        e0_cc = build_engine(g0, MIN_TIMES)
        e0_pr = build_engine(g0, PLUS_TIMES, normalize=True)
        old_levels = np.asarray(bfs_multi(e0_bool, sources).levels)
        old_dist = np.asarray(sssp_multi(e0_w, sources).dist)
        old_labels = np.asarray(connected_components(e0_cc).labels)
        old_rank = np.asarray(pagerank(e0_pr, max_iters=PR_ITERS).rank)

        for kind, delta in _deltas(g0):
            if kind == "grow":
                _apply_throughput(fam, g0, delta, reps)
            dg = DynamicGraph(g0)
            g1 = dg.apply(delta)
            d = canonicalize(delta, g0.n)
            e1_unit = build_engine(g1, MIN_PLUS, weighted=False)
            e1_bool = build_engine(g1, BOOL_OR_AND)
            e1_w = build_engine(g1, MIN_PLUS, weighted=True, seed=5,
                                content_keyed=True)
            e1_cc = build_engine(g1, MIN_TIMES)
            e1_pr = build_engine(g1, PLUS_TIMES, normalize=True)
            repair = plan_repair(e1_unit, d)

            # BFS — exactness on every batch, traffic win on grow
            cold = bfs_multi(e1_bool, sources)
            inc = bfs_incremental(e1_unit, sources, old_levels, d,
                                  repair=repair)
            np.testing.assert_array_equal(inc.values, np.asarray(cold.levels),
                                          err_msg=f"{fam}/{kind}/bfs")
            t_cold = timeit(lambda: bfs_multi(e1_bool, sources),
                            iters=reps, warmup=1)
            t_inc = timeit(lambda: bfs_incremental(
                e1_unit, sources, old_levels, d, repair=repair),
                iters=reps, warmup=1)
            traffic_cold = traffic_of(cold)
            traffic_inc = inc.traffic + repair.traffic
            if kind == "grow":
                assert traffic_inc < traffic_cold, (
                    f"{fam}/bfs incremental traffic {traffic_inc} !< "
                    f"cold {traffic_cold}")
            emit("dynamic_updates", f"{fam}/{kind}/bfs",
                 traffic_cold=traffic_cold, traffic_inc=traffic_inc,
                 wall_cold_ms=t_cold * 1e3, wall_inc_ms=t_inc * 1e3,
                 checksum=_csum(np.asarray(cold.levels)))

            # SSSP — same bar over content-keyed integer weights
            cold_w = sssp_multi(e1_w, sources)
            inc_w = sssp_incremental(e1_w, sources, old_dist, d,
                                     repair=repair)
            np.testing.assert_array_equal(
                inc_w.values, np.asarray(cold_w.dist),
                err_msg=f"{fam}/{kind}/sssp")
            t_cold = timeit(lambda: sssp_multi(e1_w, sources),
                            iters=reps, warmup=1)
            t_inc = timeit(lambda: sssp_incremental(
                e1_w, sources, old_dist, d, repair=repair),
                iters=reps, warmup=1)
            traffic_cold = traffic_of(cold_w)
            traffic_inc = inc_w.traffic + repair.traffic
            if kind == "grow":
                assert traffic_inc < traffic_cold, (
                    f"{fam}/sssp incremental traffic {traffic_inc} !< "
                    f"cold {traffic_cold}")
            emit("dynamic_updates", f"{fam}/{kind}/sssp",
                 traffic_cold=traffic_cold, traffic_inc=traffic_inc,
                 wall_cold_ms=t_cold * 1e3, wall_inc_ms=t_inc * 1e3,
                 checksum=_csum(np.asarray(cold_w.dist)))

            # CC — label repair: exact, never more rounds than cold
            cold_cc = connected_components(e1_cc)
            inc_cc = cc_incremental(e1_cc, old_labels, d)
            np.testing.assert_array_equal(
                np.asarray(inc_cc.labels), np.asarray(cold_cc.labels),
                err_msg=f"{fam}/{kind}/cc")
            if kind == "grow":
                assert int(inc_cc.iterations) <= int(cold_cc.iterations)
            emit("dynamic_updates", f"{fam}/{kind}/cc",
                 iters_cold=int(cold_cc.iterations),
                 iters_inc=int(inc_cc.iterations),
                 checksum=_csum(np.asarray(cold_cc.labels)))

            # PageRank — warm restart iteration win (dense rounds)
            cold_pr = pagerank(e1_pr, max_iters=PR_ITERS)
            warm_pr = pagerank_warm(e1_pr, old_rank, max_iters=PR_ITERS)
            np.testing.assert_allclose(
                np.asarray(warm_pr.rank), np.asarray(cold_pr.rank),
                rtol=1e-4, atol=1e-7)
            if fam in ("road", "uniform"):
                assert int(warm_pr.iterations) < int(cold_pr.iterations), (
                    f"{fam}/{kind} warm pagerank took "
                    f"{int(warm_pr.iterations)} >= {int(cold_pr.iterations)}")
            emit("dynamic_updates", f"{fam}/{kind}/pagerank",
                 iters_cold=int(cold_pr.iterations),
                 iters_warm=int(warm_pr.iterations))

    _server_retention(quick)


def _server_retention(quick: bool):
    """Prove selective invalidation through the serving stack: a delta
    confined to the giant component must invalidate its entries while
    cached answers for other components migrate across the version bump
    and keep hitting (road dropout guarantees several components)."""
    g = datasets.road_graph(900 if quick else 2500, 2.4, seed=2)
    from repro.graphs.analytics import cc_reference
    labels = cc_reference(g.rows, g.cols, g.n)
    uniq, counts = np.unique(labels, return_counts=True)
    big = int(uniq[np.argmax(counts)])
    others = [int(np.nonzero(labels == u)[0][0])
              for u, c in zip(uniq, counts) if u != big and c >= 2][:3]
    assert others, "road dropout should leave small components"
    big_nodes = np.nonzero(labels == big)[0]

    srv = GraphQueryServer(g, batch_size=4, cache_capacity=256)
    for s in others:
        srv.submit("bfs", s)
        srv.submit("sssp", s)
    srv.submit("bfs", int(big_nodes[0]))
    srv.flush()
    ins = np.stack([big_nodes[3:11], big_nodes[20:28]], 1)
    report = srv.mutate(EdgeDelta(insert_rows=ins[:, 0],
                                  insert_cols=ins[:, 1]))
    stats = srv.stats()
    assert report["retained"] >= 2 * len(others), report
    assert report["invalidated"] >= 1, report
    assert stats["entries_retained"] == report["retained"]
    hits_before = stats["cache"]["hits"]
    for s in others:
        srv.submit("bfs", s)
    srv.flush()
    assert srv.stats()["cache"]["hits"] == hits_before + len(others), (
        "migrated entries must keep serving after mutate")
    emit("dynamic_updates", "road/server_mutate",
         retained=report["retained"], invalidated=report["invalidated"],
         version=srv.version)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    run(quick=ap.parse_args().quick)

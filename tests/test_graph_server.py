"""GraphQueryServer: batching, source dedup, LRU caching, global
(whole-graph) request kinds, graph-keyed cache safety, and answer fidelity
against the single-source apps (serve/graph_engine.py)."""
import numpy as np
import pytest

from repro.core.delta import EdgeDelta
from repro.graphs import bfs, generate, ppr, sssp
from repro.graphs.analytics import connected_components, kcore, triangle_count
from repro.graphs.ppr import pagerank
from repro.serve.graph_engine import GraphQueryServer, LRUCache


@pytest.fixture(scope="module")
def graph():
    return generate("face", scale=0.15, seed=1)


@pytest.fixture()
def server(graph):
    return GraphQueryServer(graph, batch_size=4, cache_capacity=64)


def test_results_match_single_source(server, graph):
    rng = np.random.default_rng(0)
    srcs = [int(s) for s in rng.integers(0, graph.n, 5)]
    reqs = [server.submit("bfs", s) for s in srcs]
    reqs += [server.submit("sssp", srcs[0]), server.submit("ppr", srcs[1])]
    done = server.flush()
    assert done == reqs and all(r.result is not None for r in done)

    ref = bfs(server.engine("bfs"), srcs[2])
    got = done[2].result
    np.testing.assert_array_equal(got["levels"], np.asarray(ref.levels))
    assert got["iterations"] == int(ref.iterations)

    ref_s = sssp(server.engine("sssp"), srcs[0])
    np.testing.assert_allclose(done[5].result["dist"],
                               np.asarray(ref_s.dist), rtol=1e-6)
    ref_p = ppr(server.engine("ppr"), srcs[1])
    np.testing.assert_allclose(done[6].result["rank"],
                               np.asarray(ref_p.rank), rtol=1e-5, atol=1e-8)


def test_dedup_and_cache(server, graph):
    s = int(graph.n // 2)
    r1 = server.submit("bfs", s)
    r2 = server.submit("bfs", s)          # same flush -> deduped
    server.flush()
    assert server.stats()["deduped"] == 1
    assert server.stats()["batches"] == 1   # one padded batch for one source
    np.testing.assert_array_equal(r1.result["levels"], r2.result["levels"])
    assert not r1.cached and not r2.cached

    r3 = server.submit("bfs", s)          # later flush -> LRU hit
    server.flush()
    assert r3.cached and server.stats()["cache_hits"] == 1
    assert server.stats()["batches"] == 1   # engine never re-ran
    np.testing.assert_array_equal(r3.result["levels"], r1.result["levels"])


def test_batching_chunks_large_floods(server, graph):
    srcs = list(range(10))                # 10 distinct > batch_size=4
    for s in srcs:
        server.submit("bfs", s)
    done = server.flush()
    assert len(done) == 10
    assert server.stats()["batches"] == 3   # ceil(10 / 4)
    assert all(r.result is not None for r in done)


def test_submit_validation(server, graph):
    with pytest.raises(ValueError):
        server.submit("pagerank_global", 0)
    with pytest.raises(ValueError):
        server.submit("bfs", graph.n + 5)


def test_lru_eviction_bound():
    c = LRUCache(capacity=2)
    c.put(("bfs", 1), {"a": 1})
    c.put(("bfs", 2), {"a": 2})
    c.put(("bfs", 3), {"a": 3})
    assert len(c) == 2
    assert c.get(("bfs", 1)) is None      # evicted (oldest)
    assert c.get(("bfs", 3)) is not None
    # touching 2 makes 3 the eviction candidate
    c.get(("bfs", 2))
    c.put(("bfs", 4), {"a": 4})
    assert c.get(("bfs", 2)) is not None and c.get(("bfs", 3)) is None


def test_global_queries_match_apps(server, graph):
    """Whole-graph kinds ride the same submit/flush path and agree with
    direct app calls."""
    reqs = {alg: server.submit(alg)
            for alg in ("cc", "pagerank", "triangles", "kcore")}
    reqs["bfs"] = server.submit("bfs", 0)   # mixed flush
    done = server.flush()
    assert len(done) == 5 and all(r.result is not None for r in done)

    ref_cc = connected_components(server.engine("cc"))
    np.testing.assert_array_equal(reqs["cc"].result["labels"],
                                  np.asarray(ref_cc.labels))
    assert reqs["cc"].result["n_components"] == int(ref_cc.n_components)

    ref_pr = pagerank(server.engine("pagerank"), alpha=server.alpha,
                      max_iters=server.max_iters)
    np.testing.assert_allclose(reqs["pagerank"].result["rank"],
                               np.asarray(ref_pr.rank), rtol=1e-5, atol=1e-8)

    assert reqs["triangles"].result["total"] == int(triangle_count(graph).total)

    ref_kc = kcore(server.engine("kcore"))
    np.testing.assert_array_equal(reqs["kcore"].result["coreness"],
                                  np.asarray(ref_kc.coreness))


def test_global_computed_once_and_fanned_out(server, graph):
    """N askers in one flush share one run; the first miss computes and
    caches, the rest resolve as ordinary LRU hits (per-request probing, so
    stats['cache_hits'] reconciles with LRUCache.hits across query kinds)."""
    reqs = [server.submit("cc") for _ in range(3)]
    server.flush()
    assert server.stats()["global_runs"] == 1
    assert not reqs[0].cached and reqs[1].cached and reqs[2].cached
    assert server.stats()["cache_hits"] == 2 == server.cache.hits
    for r in reqs[1:]:
        np.testing.assert_array_equal(r.result["labels"],
                                      reqs[0].result["labels"])
    r4 = server.submit("cc")
    server.flush()
    assert r4.cached and server.stats()["global_runs"] == 1
    assert server.stats()["cache_hits"] == 3 == server.cache.hits
    np.testing.assert_array_equal(r4.result["labels"],
                                  reqs[0].result["labels"])


def test_global_compute_once_with_caching_disabled(graph):
    """The compute-once contract must not depend on the LRU accepting
    puts: with cache_capacity=0, N askers in one flush still share one
    run (counted as dedup, like the traversal path)."""
    srv = GraphQueryServer(graph, cache_capacity=0)
    reqs = [srv.submit("cc") for _ in range(4)]
    srv.flush()
    assert srv.stats()["global_runs"] == 1
    assert srv.stats()["deduped"] == 3 and srv.stats()["cache_hits"] == 0
    for r in reqs[1:]:
        np.testing.assert_array_equal(r.result["labels"],
                                      reqs[0].result["labels"])


def test_triangles_dense_limit_fallback(graph):
    """Above triangle_dense_limit the server answers triangles via the
    nnz-scaled sequential counter instead of the dense-operand SpGEMM —
    same exact total, no O(n²) allocation on the serve path."""
    srv = GraphQueryServer(graph, triangle_dense_limit=1)
    req = srv.submit("triangles")
    srv.flush()
    assert req.result["total"] == int(triangle_count(graph).total)


def test_global_submit_validation(server):
    with pytest.raises(ValueError):
        server.submit("cc", 0)        # global kinds take no source
    with pytest.raises(ValueError):
        server.submit("triangles", 3)


def test_shared_cache_keys_max_iters_by_kind(graph):
    """``max_iters`` bounds PPR alone: two servers that differ only in it
    share their BFS answers through one cache, never their PPR ones."""
    shared = LRUCache(128)
    s1 = GraphQueryServer(graph, batch_size=4, cache=shared, max_iters=64)
    s2 = GraphQueryServer(graph, batch_size=4, cache=shared, max_iters=8)
    assert s1.engine_key == s2.engine_key
    a, p = s1.submit("bfs", 3), s1.submit("ppr", 3)
    s1.flush()
    b, q = s2.submit("bfs", 3), s2.submit("ppr", 3)
    s2.flush()
    assert b.cached and not q.cached
    np.testing.assert_array_equal(b.result["levels"], a.result["levels"])
    assert q.result["iterations"] <= 8 < p.result["iterations"]


def test_shared_cache_keys_by_graph_identity(graph):
    """Regression (ISSUE 2 satellite): one cache serving two graphs (or a
    rebuilt engine) must never return stale cross-graph results."""
    shared = LRUCache(128)
    other = generate("face", scale=0.15, seed=7)   # same sizes, new edges
    s1 = GraphQueryServer(graph, batch_size=4, cache=shared)
    s2 = GraphQueryServer(other, batch_size=4, cache=shared)
    assert s1.engine_key != s2.engine_key

    a = s1.submit("bfs", 3)
    s1.flush()
    b = s2.submit("bfs", 3)
    s2.flush()
    assert not b.cached                      # miss: different graph content
    ref = bfs(s2.engine("bfs"), 3)
    np.testing.assert_array_equal(b.result["levels"], np.asarray(ref.levels))

    t1 = s1.submit("triangles"); s1.flush()
    t2 = s2.submit("triangles"); s2.flush()
    assert not t2.cached
    assert t2.result["total"] == int(triangle_count(other).total)

    # same edge content in a rebuilt Graph object -> cache HIT (fingerprint
    # is content-addressed, not object identity)
    rebuilt = generate("face", scale=0.15, seed=1)
    s3 = GraphQueryServer(rebuilt, batch_size=4, cache=shared)
    assert s3.engine_key == s1.engine_key
    c = s3.submit("bfs", 3)
    s3.flush()
    assert c.cached
    np.testing.assert_array_equal(c.result["levels"], a.result["levels"])


def test_engine_param_changes_miss_cache(graph):
    """A server with different engine parameters (weight seed) must not
    reuse another's SSSP distances."""
    shared = LRUCache(128)
    s1 = GraphQueryServer(graph, batch_size=4, cache=shared, weight_seed=5)
    s2 = GraphQueryServer(graph, batch_size=4, cache=shared, weight_seed=6)
    a = s1.submit("sssp", 1); s1.flush()
    b = s2.submit("sssp", 1); s2.flush()
    assert not b.cached
    ref = sssp(s2.engine("sssp"), 1)
    np.testing.assert_allclose(b.result["dist"], np.asarray(ref.dist),
                               rtol=1e-6)
    assert a.result is not b.result


def test_flush_pipelining_equality(graph):
    """The pipelined flush drain (pipeline_depth > 0) must return results
    bit-identical to the sequential drain (depth 0) for a multi-bucket,
    multi-algorithm flood — the bucket pipeline moves host sync points,
    never answers (ISSUE-3 acceptance).  The pipelined server also runs
    with the partition planner's strategy="auto" (ISSUE-4 acceptance: the
    planning decision never changes served answers)."""
    seq = GraphQueryServer(graph, batch_size=4, cache_capacity=0,
                           pipeline_depth=0)
    pip = GraphQueryServer(graph, batch_size=4, cache_capacity=0,
                           pipeline_depth=3, strategy="auto")
    srcs = list(range(10))               # 3 buckets per algorithm
    for alg in ("bfs", "sssp", "ppr"):
        for s in srcs:
            seq.submit(alg, s)
            pip.submit(alg, s)
    done_seq, done_pip = seq.flush(), pip.flush()
    assert len(done_seq) == len(done_pip) == 30
    assert seq.stats()["batches"] == pip.stats()["batches"] == 9
    for a, b in zip(done_seq, done_pip):
        assert (a.algorithm, a.source) == (b.algorithm, b.source)
        assert a.result.keys() == b.result.keys()
        for key, val in a.result.items():
            np.testing.assert_array_equal(np.asarray(val),
                                          np.asarray(b.result[key]))


def test_partition_strategy_resolution(graph):
    """strategy="auto" resolves through the cost-model planner at
    construction; fixed specs pin strategy/balance; bad specs fail fast.
    The choice is recorded but never enters the cache key (it cannot
    change answers)."""
    auto = GraphQueryServer(graph, strategy="auto")
    assert auto.partition_choice.strategy in ("row", "col", "2d")
    assert auto.partition_choice.balance in ("rows", "nnz")
    # auto never picks a plan more skewed than the worst candidate
    worst = max(c["imbalance"] for c in auto.partition_choice.costs.values())
    assert auto.partition_choice.plan.imbalance() <= worst + 1e-9

    fixed = GraphQueryServer(graph, strategy="row:nnz")
    assert fixed.partition_choice.strategy == "row"
    assert fixed.partition_choice.balance == "nnz"
    assert fixed.engine_key == auto.engine_key   # not answer-shaping

    with pytest.raises(ValueError):
        GraphQueryServer(graph, strategy="diagonal")
    with pytest.raises(ValueError):
        GraphQueryServer(graph, strategy="row:fair")


def test_lru_counters_and_stats_accessor(graph):
    """The ISSUE-5 satellite: hit/miss/eviction counters on the LRU and a
    coherent GraphQueryServer.stats() snapshot."""
    c = LRUCache(capacity=2)
    assert c.stats() == {"lookups": 0, "hits": 0, "misses": 0,
                         "evictions": 0, "size": 0, "capacity": 2}
    c.put(("k", "bfs", 1), {}); c.put(("k", "bfs", 2), {})
    c.put(("k", "bfs", 3), {})            # evicts 1
    c.get(("k", "bfs", 3)); c.get(("k", "bfs", 1))
    assert c.stats() == {"lookups": 2, "hits": 1, "misses": 1,
                         "evictions": 1, "size": 2, "capacity": 2}

    srv = GraphQueryServer(graph, batch_size=4)
    srv.submit("bfs", 1); srv.flush()
    st = srv.stats()
    assert st["submitted"] == st["served"] == 1
    assert st["version"] == 0
    assert st["cache"] == srv.cache.stats()


def test_stats_returns_a_deep_copy(graph):
    """The ISSUE-7 satellite regression: mutating any nesting level of a
    stats() snapshot must never write through to the server's live
    counters, cache stats, or latency instruments."""
    srv = GraphQueryServer(graph, batch_size=4)
    srv.submit("bfs", 1); srv.flush()
    st = srv.stats()
    st["served"] = 999
    st["cache"]["hits"] = 999
    st["latency"]["queue_depth"]["max"] = 999.0
    st["latency"]["flush_s"]["count"] = 999
    st["latency"]["lru_hit_rate"] = 999.0
    fresh = srv.stats()
    assert fresh["served"] == 1
    assert fresh["cache"]["hits"] != 999
    assert fresh["latency"]["queue_depth"]["max"] != 999.0
    assert fresh["latency"]["flush_s"]["count"] == 1
    assert fresh["latency"]["lru_hit_rate"] != 999.0
    assert st is not fresh and st["cache"] is not fresh["cache"]


def test_stats_latency_section(graph):
    """stats()["latency"]: per-flush and per-query latency accounting
    from the server's private MetricsRegistry (the ISSUE-7 tentpole's
    serve-layer instrumentation)."""
    srv = GraphQueryServer(graph, batch_size=4)
    lat0 = srv.stats()["latency"]
    assert lat0["queue_depth"]["writes"] == 0     # nothing flushed yet

    for s in (1, 2, 3, 4, 5):
        srv.submit("bfs", s)
    srv.flush()
    srv.submit("bfs", 1); srv.flush()             # a cache-hit flush
    lat = srv.stats()["latency"]

    assert lat["queue_depth"]["max"] == 5.0 and \
        lat["queue_depth"]["writes"] == 2
    assert lat["enqueue_wait_s"]["count"] == 6    # every request waited
    assert lat["enqueue_wait_s"]["min"] >= 0.0
    assert lat["flush_s"]["count"] == 2
    assert lat["flush_s"]["p50"] <= lat["flush_s"]["max"]
    # 5 deduped sources / batch_size 4 -> two padded batches, then none
    assert lat["batch_size"]["count"] == 2
    assert lat["batch_size"]["max"] == 4.0
    assert lat["bucket_s"]["count"] == 2
    assert lat["lru_hit_rate"] > 0.0              # the second flush hit
    import json as _json
    _json.dumps(srv.stats())                      # snapshot stays JSON-safe


def _delta_for(graph):
    """A delta confined to the largest component, plus the sources whose
    cached answers must survive it (picked from other components)."""
    from repro.graphs.analytics import cc_reference
    labels = cc_reference(graph.rows, graph.cols, graph.n)
    uniq, counts = np.unique(labels, return_counts=True)
    big = int(uniq[np.argmax(counts)])
    big_nodes = np.nonzero(labels == big)[0]
    ins = np.stack([big_nodes[2:6], big_nodes[8:12]], 1)
    outside = [int(np.nonzero(labels == u)[0][0])
               for u, c in zip(uniq, counts) if u != big][:2]
    delta = EdgeDelta(insert_rows=ins[:, 0], insert_cols=ins[:, 1],
                      delete_rows=[graph.rows[int(np.nonzero(
                          labels[graph.rows] == big)[0][0])]],
                      delete_cols=[graph.cols[int(np.nonzero(
                          labels[graph.rows] == big)[0][0])]])
    return delta, int(big_nodes[0]), outside


@pytest.fixture(scope="module")
def split_graph():
    # road dropout leaves several components — the retention scenario
    return generate("r-TX", scale=0.001, seed=3)


def test_mutate_selectively_invalidates(split_graph):
    """mutate() must migrate entries the delta provably cannot reach to
    the new fingerprint (they keep hitting) and drop the rest — the
    all-or-nothing fingerprint flush is gone (ISSUE-5 acceptance)."""
    delta, inside, outside = _delta_for(split_graph)
    assert outside, "fixture graph must have several components"
    srv = GraphQueryServer(split_graph, batch_size=4, cache_capacity=128)
    keep_reqs = {}
    for s in outside:
        keep_reqs[s] = (srv.submit("bfs", s), srv.submit("sssp", s))
    srv.submit("bfs", inside)
    srv.submit("cc")
    srv.flush()
    old_key = srv.engine_key

    report = srv.mutate(delta)
    assert srv.version == 1 and srv.engine_key != old_key
    assert report["retained"] == 2 * len(outside)
    assert report["invalidated"] == 2          # inside-bfs + global cc
    st = srv.stats()
    assert st["entries_retained"] == report["retained"]
    assert st["entries_invalidated"] == report["invalidated"]
    assert st["mutations"] == 1 and st["version"] == 1

    # survivors keep serving from cache — and stay exact on the new graph
    hits0 = srv.stats()["cache"]["hits"]
    for s in outside:
        r = srv.submit("bfs", s); srv.flush()
        assert r.cached
        ref = bfs(srv.engine("bfs"), s)
        np.testing.assert_array_equal(r.result["levels"],
                                      np.asarray(ref.levels))
        rs = srv.submit("sssp", s); srv.flush()
        assert rs.cached
        ref_s = sssp(srv.engine("sssp"), s)
        np.testing.assert_array_equal(rs.result["dist"],
                                      np.asarray(ref_s.dist))
    assert srv.stats()["cache"]["hits"] == hits0 + 2 * len(outside)

    # invalidated entries recompute against the new snapshot
    r = srv.submit("bfs", inside); srv.flush()
    assert not r.cached
    ref = bfs(srv.engine("bfs"), inside)
    np.testing.assert_array_equal(r.result["levels"], np.asarray(ref.levels))


def test_mutate_drains_inflight_queue_against_old_snapshot(split_graph):
    """Requests queued before mutate() observe the pre-mutation graph."""
    delta, inside, _outside = _delta_for(split_graph)
    srv = GraphQueryServer(split_graph, batch_size=4)
    ref_old = bfs(srv.engine("bfs"), inside)     # old-snapshot oracle
    req = srv.submit("bfs", inside)              # left queued
    srv.mutate(delta)
    assert req.result is not None, "mutate must flush the queue first"
    np.testing.assert_array_equal(req.result["levels"],
                                  np.asarray(ref_old.levels))
    # ... and a fresh query sees the new snapshot
    req2 = srv.submit("bfs", inside); srv.flush()
    ref_new = bfs(srv.engine("bfs"), inside)
    np.testing.assert_array_equal(req2.result["levels"],
                                  np.asarray(ref_new.levels))


def test_mutate_noop_keeps_cache(split_graph):
    """Inserting present edges / deleting absent ones is a no-op epoch:
    version bumps, fingerprint (and so every cache key) survives."""
    srv = GraphQueryServer(split_graph, batch_size=4)
    r = srv.submit("bfs", 0); srv.flush()
    assert r.result is not None
    key = srv.engine_key
    u, v = int(split_graph.rows[0]), int(split_graph.cols[0])
    report = srv.mutate(EdgeDelta(insert_rows=[u], insert_cols=[v]))
    assert report == {"version": 1, "inserted": 0, "deleted": 0,
                      "retained": 0, "invalidated": 0, "replanned": False}
    assert srv.engine_key == key
    r2 = srv.submit("bfs", 0); srv.flush()
    assert r2.cached


def test_mutate_repairs_partition_choice(split_graph):
    """A computed partition_choice survives mutation via incremental plan
    repair; its tile counts track the new snapshot's nnz."""
    delta, _inside, _outside = _delta_for(split_graph)
    srv = GraphQueryServer(split_graph, strategy="auto")
    choice0 = srv.partition_choice                  # force computation
    srv.mutate(delta)
    st = srv.stats()
    assert st["plan_repairs"] + st["plan_replans"] == 1
    assert sum(srv.partition_choice.plan.tile_nnz) == srv.graph.nnz
    assert srv.partition_choice is not choice0


def test_mutate_global_entries_always_invalidate(split_graph):
    """Whole-graph kinds see every edge: any effective delta must drop
    them, and the next ask recomputes on the new snapshot."""
    delta, _inside, _outside = _delta_for(split_graph)
    srv = GraphQueryServer(split_graph, batch_size=4)
    srv.submit("cc"); srv.flush()
    assert srv.stats()["global_runs"] == 1
    srv.mutate(delta)
    r = srv.submit("cc"); srv.flush()
    assert not r.cached and srv.stats()["global_runs"] == 2
    ref = connected_components(srv.engine("cc"))
    np.testing.assert_array_equal(r.result["labels"], np.asarray(ref.labels))


def test_mixed_algorithms_one_flush(server, graph):
    rng = np.random.default_rng(5)
    subs = [(alg, int(s)) for alg in ("bfs", "sssp", "ppr")
            for s in rng.integers(0, graph.n, 2)]
    reqs = [server.submit(a, s) for a, s in subs]
    server.flush()
    for (alg, _s), req in zip(subs, reqs):
        key = {"bfs": "levels", "sssp": "dist", "ppr": "rank"}[alg]
        assert key in req.result and req.result["iterations"] >= 1

"""Batched multi-source traversal equivalence: every row of a B=8 batch
must match the corresponding single-source run — outputs, per-query
iteration counts, the trips the adaptive switch sent to the SpMV and the
frontier entries read — on both a scale-free and a regular synthetic
graph."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
from repro.graphs import (
    bfs, bfs_multi, generate, ppr, ppr_multi, sssp, sssp_multi,
    traverse_multi_buckets,
)
from repro.graphs.cost_model import trained_stump
from repro.graphs.engine import build_engine
from repro.graphs.multi import make_bfs_multi, make_ppr_multi, make_sssp_multi

B = 8
GRAPHS = {
    "scale_free": ("face", 0.15),    # heavy-tailed -> 50% switch threshold
    "regular": ("p2p-24", 0.12),     # low-variance -> 20% switch threshold
}


@pytest.fixture(scope="module")
def stump():
    return trained_stump()


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph_and_sources(request):
    abbrev, scale = GRAPHS[request.param]
    g = generate(abbrev, scale=scale, seed=1)
    rng = np.random.default_rng(42)
    sources = [int(s) for s in rng.integers(0, g.n, B)]
    return request.param, g, sources


def _check_traces(batch_res, single_res, i):
    """Row ``i``'s counters equal the single-source run's: its trips, its
    trips on the SpMV and the frontier entries it read."""
    for field in ("iterations", "spmv_trips", "consumed"):
        assert int(getattr(batch_res, field)[i]) == int(
            getattr(single_res, field)), field


@pytest.mark.parametrize("policy", ["adaptive", "spmv", "spmspv"])
def test_bfs_multi_matches_single(graph_and_sources, stump, policy):
    cls, g, sources = graph_and_sources
    eng = build_engine(g, BOOL_OR_AND, stump)
    assert eng.graph_class == ("scale_free" if cls == "scale_free"
                               else "regular")
    res = bfs_multi(eng, sources, policy=policy)
    for i, s in enumerate(sources):
        ref = bfs(eng, s, policy=policy)
        np.testing.assert_array_equal(np.asarray(res.levels[i]),
                                      np.asarray(ref.levels))
        _check_traces(res, ref, i)


def test_sssp_multi_matches_single(graph_and_sources, stump):
    _cls, g, sources = graph_and_sources
    eng = build_engine(g, MIN_PLUS, stump, weighted=True, seed=5)
    res = sssp_multi(eng, sources)
    for i, s in enumerate(sources):
        ref = sssp(eng, s)
        np.testing.assert_allclose(np.asarray(res.dist[i]),
                                   np.asarray(ref.dist), rtol=1e-6)
        _check_traces(res, ref, i)


def test_ppr_multi_matches_single(graph_and_sources, stump):
    _cls, g, sources = graph_and_sources
    eng = build_engine(g, PLUS_TIMES, stump, normalize=True)
    res = ppr_multi(eng, sources)
    for i, s in enumerate(sources):
        ref = ppr(eng, s)
        np.testing.assert_allclose(np.asarray(res.rank[i]),
                                   np.asarray(ref.rank), rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(float(res.residual[i]),
                                   float(ref.residual), rtol=1e-4, atol=1e-9)
        _check_traces(res, ref, i)


def test_multi_freezes_converged_queries(stump):
    """A batch mixing trivially-convergent and long-running queries must
    freeze the early finishers: per-query iteration counts differ inside
    one batched while_loop."""
    g = generate("face", scale=0.15, seed=1)
    eng = build_engine(g, BOOL_OR_AND, stump)
    deg = np.bincount(g.rows, minlength=g.n)
    hub = int(np.argmax(deg))
    # an isolated-ish vertex: minimal out-degree (BFS from it ends fast)
    lone = int(np.argmin(deg + (deg == 0) * g.n))
    res = bfs_multi(eng, [hub, lone, hub, lone])
    iters = np.asarray(res.iterations)
    assert iters[0] == iters[2] and iters[1] == iters[3]
    ref_hub, ref_lone = bfs(eng, hub), bfs(eng, lone)
    assert iters[0] == int(ref_hub.iterations)
    assert iters[1] == int(ref_lone.iterations)
    # a frozen query's counters stop counting
    for i, ref in ((0, ref_hub), (1, ref_lone)):
        _check_traces(res, ref, i)


@pytest.mark.parametrize("alg", ["bfs", "sssp", "ppr"])
def test_bucket_pipeline_matches_sequential(graph_and_sources, stump, alg):
    """traverse_multi_buckets: the pipelined drain (depths 1/2) must be
    bit-identical to the sequential depth-0 drain on identical buckets,
    and every row must match the single-source app (the ISSUE-3 pipelined
    traversal equality, bucket granularity)."""
    _cls, g, sources = graph_and_sources
    if alg == "bfs":
        eng = build_engine(g, BOOL_OR_AND, stump)
        single, field, exact = bfs, "levels", True
    elif alg == "sssp":
        eng = build_engine(g, MIN_PLUS, stump, weighted=True, seed=5)
        single, field, exact = sssp, "dist", False
    else:
        eng = build_engine(g, PLUS_TIMES, stump, normalize=True)
        single, field, exact = ppr, "rank", False
    buckets = [sources[:4], sources[4:]]
    blocking = traverse_multi_buckets(eng, alg, buckets, pipeline_depth=0)
    for depth in (1, 2):
        pipelined = traverse_multi_buckets(eng, alg, buckets,
                                           pipeline_depth=depth)
        for res_b, res_p in zip(blocking, pipelined):
            for arr_b, arr_p in zip(res_b, res_p):
                np.testing.assert_array_equal(np.asarray(arr_b),
                                              np.asarray(arr_p))
    for bucket, res in zip(buckets, blocking):
        for i, s in enumerate(bucket):
            ref = np.asarray(getattr(single(eng, s), field))
            got = np.asarray(getattr(res, field)[i])
            if exact:
                np.testing.assert_array_equal(got, ref)
            else:
                np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-8)


def test_bucket_pipeline_mixed_sizes_and_order(stump):
    """Mixed-size buckets compile one runner per size and come back in
    submission order at any depth."""
    g = generate("face", scale=0.15, seed=1)
    eng = build_engine(g, BOOL_OR_AND, stump)
    rng = np.random.default_rng(9)
    srcs = [int(s) for s in rng.integers(0, g.n, 7)]
    buckets = [srcs[:4], srcs[4:6], srcs[6:]]    # sizes 4, 2, 1
    out = traverse_multi_buckets(eng, "bfs", buckets, pipeline_depth=3)
    assert [r.levels.shape[0] for r in out] == [4, 2, 1]
    for bucket, res in zip(buckets, out):
        for i, s in enumerate(bucket):
            ref = bfs(eng, s)
            np.testing.assert_array_equal(np.asarray(res.levels[i]),
                                          np.asarray(ref.levels))


def test_batched_closures_match_unbatched(stump):
    """Engine-level check: spmv_batch_fn/spmspv_batch_fn rows equal the
    single-vector closures on the same inputs."""
    import jax.numpy as jnp
    g = generate("face", scale=0.15, seed=1)
    eng = build_engine(g, PLUS_TIMES, stump, normalize=True)
    rng = np.random.default_rng(0)
    xs = np.where(rng.random((4, eng.n)) < 0.1,
                  rng.random((4, eng.n)), 0.0).astype(np.float32)
    xs_j = jnp.asarray(xs)
    ys_mv = np.asarray(eng.spmv_batch_fn(xs_j))
    ys_msv = np.asarray(eng.spmspv_batch_fn(xs_j))
    for i in range(4):
        np.testing.assert_allclose(ys_mv[i], np.asarray(eng.spmv_fn(xs_j[i])),
                                   rtol=1e-6)
        np.testing.assert_allclose(ys_msv[i],
                                   np.asarray(eng.spmspv_fn(xs_j[i])),
                                   rtol=1e-6)


def _ring_graph(n: int, k: int):
    """Vertex i -> i+1..i+k (mod n): every column holds exactly k entries,
    so two rings differing only in k build the same rung ladder."""
    from repro.graphs.datasets import Graph

    src = np.repeat(np.arange(n), k)
    dst = (src + np.tile(np.arange(1, k + 1), n)) % n
    return Graph(src.astype(np.int32), dst.astype(np.int32), n, f"ring{k}")


def test_bfs_runner_takes_graph_as_arguments():
    """The served runner lowers the engine's matrices as parameters of
    ``main``, not as constants: graphs whose nnz differs by 8x lower to
    programs of (almost) the same size."""
    from repro.graphs.multi import make_bfs_multi

    sizes = []
    for k in (2, 16):
        eng = build_engine(_ring_graph(4096, k), BOOL_OR_AND)
        run = make_bfs_multi(eng, batch=B)
        lowered = run.jitted.lower(run.mats, np.zeros(B, np.int32))
        text = lowered.as_text()
        main = next(ln for ln in text.splitlines() if "@main(" in ln)
        # every per-entry array the traversal reads (CSR cols/vals/seg ids,
        # CSC rows/vals) is a parameter; unread leaves are pruned
        nnz_max = eng.mats[0].nnz_max
        assert main.count(f"tensor<{nnz_max}x") >= 4, main
        sizes.append(len(text))
        res = run(np.arange(B, dtype=np.int32))
        ref = bfs_multi(eng, list(range(B)))
        np.testing.assert_array_equal(np.asarray(res.levels),
                                      np.asarray(ref.levels))
    assert abs(sizes[1] - sizes[0]) < 0.01 * sizes[0], sizes


class _NoScope(contextlib.ContextDecorator):
    """Stands in for ``jax.named_scope``: no name, no metadata."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


SCOPED = {
    "bfs": (make_bfs_multi, BOOL_OR_AND, {}, "bfs_step"),
    "sssp": (make_sssp_multi, MIN_PLUS, {"weighted": True, "seed": 5},
             "sssp_step"),
    "ppr": (make_ppr_multi, PLUS_TIMES, {"normalize": True}, "ppr_step"),
}


def _scoped_run(alg, g, stump, sources):
    """(op_name scopes of the compiled runner, its result) on a fresh
    engine, so no runner compiled under another scoping is reused."""
    make, sr, kw, _step = SCOPED[alg]
    runner = make(build_engine(g, sr, stump, **kw), len(sources))
    src = jnp.asarray(sources, jnp.int32)
    hlo = runner.jitted.lower(runner.mats, src).compile().as_text()
    # every component of each op's name path but the op itself
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', hlo)
              for part in name.split("/")[:-1]}
    return scopes, jax.device_get(runner(src))


@pytest.mark.parametrize("alg", sorted(SCOPED))
def test_runner_ops_carry_name_scopes(alg, stump, monkeypatch):
    """The compiled runner's HLO metadata names its loop step, the input
    gathers, the union compaction and the segment reduce; the scopes are
    metadata only, so the answers equal an unscoped build's bit for bit."""
    g = generate("face", scale=0.15, seed=1)
    sources = [0, 3, 5, 7]
    scopes, got = _scoped_run(alg, g, stump, sources)
    assert {SCOPED[alg][3], "segment_reduce", "gather", "frontier",
            "spmv_batch", "spmspv_union"} <= scopes
    monkeypatch.setattr(jax, "named_scope", lambda name: _NoScope())
    bare, want = _scoped_run(alg, g, stump, sources)
    assert not {SCOPED[alg][3], "segment_reduce", "gather"} & bare
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

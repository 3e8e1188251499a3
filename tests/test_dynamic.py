"""Streaming-update subsystem (ISSUE 5): EdgeDelta set algebra against
from-scratch datasets construction on every edge case, DynamicGraph
versioned snapshots, incremental recompute (BFS/SSSP delta re-relaxation,
CC label repair, warm PageRank) element-equal to cold recompute, and
incremental partition-plan repair with the imbalance-drift replan check."""
import numpy as np
import pytest

from repro.core.delta import (
    EdgeDelta, apply_edge_delta, canonicalize, edge_diff, touched_vertices,
)
from repro.core.partition import plan_partition
from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, MIN_TIMES, PLUS_TIMES
from repro.graphs import datasets
from repro.graphs.analytics import cc_reference, connected_components
from repro.graphs.datasets import Graph
from repro.graphs.dynamic import (
    DynamicGraph, bfs_incremental, cc_incremental, pagerank_warm,
    plan_repair, sssp_incremental, traffic_of,
)
from repro.graphs.engine import build_engine, content_keyed_weights
from repro.graphs.multi import bfs_multi, relax_multi, sssp_multi
from repro.graphs.ppr import pagerank


def _from_scratch(undirected_pairs, n, name="scratch") -> Graph:
    """Datasets-style construction over an undirected edge list: the
    oracle every delta-applied snapshot must match bit-for-bit."""
    arr = np.asarray(undirected_pairs, np.int64).reshape(-1, 2)
    rows, cols = datasets._symmetrize(arr[:, 0], arr[:, 1], n)
    return Graph(rows, cols, n, name)


def _assert_same_edges(g_got: Graph, g_want: Graph):
    np.testing.assert_array_equal(g_got.rows, g_want.rows)
    np.testing.assert_array_equal(g_got.cols, g_want.cols)


@pytest.fixture(scope="module")
def base():
    return datasets.road_graph(700, 2.5, seed=3)


# ---------------------------------------------------------------------------
# Delta set algebra — every edge case vs from-scratch construction
# ---------------------------------------------------------------------------

def test_empty_delta_is_identity(base):
    dg = DynamicGraph(base)
    fp0 = dg.fingerprint
    g1 = dg.apply(EdgeDelta())
    _assert_same_edges(g1, base)
    assert dg.version == 1
    # version-monotonic fingerprint: same content, new epoch prefix
    assert dg.fingerprint != fp0
    assert dg.fingerprint.split(":")[1] == fp0.split(":")[1]


def test_delete_nonexistent_edge_is_noop(base):
    # a vertex pair that is NOT an edge
    present = set(base.rows.astype(np.int64) * base.n + base.cols)
    u = 0
    v = next(w for w in range(1, base.n) if u * base.n + w not in present)
    g1 = DynamicGraph(base).apply(EdgeDelta(delete_rows=[u], delete_cols=[v]))
    _assert_same_edges(g1, base)


def test_insert_duplicate_edge_is_noop(base):
    u, v = int(base.rows[0]), int(base.cols[0])
    g1 = DynamicGraph(base).apply(EdgeDelta(insert_rows=[u], insert_cols=[v]))
    _assert_same_edges(g1, base)
    # ... and the effective diff agrees there is nothing to do
    eff = edge_diff(base.rows, base.cols, g1.rows, g1.cols, base.n)
    assert eff.n_inserts == 0 and eff.n_deletes == 0


def test_delta_on_empty_graph():
    n = 64
    empty = Graph(np.zeros(0, np.int32), np.zeros(0, np.int32), n, "empty")
    pairs = [(0, 1), (1, 2), (2, 2), (5, 4), (0, 1)]  # dup + self loop
    g1 = DynamicGraph(empty).apply(
        EdgeDelta(insert_rows=[p[0] for p in pairs],
                  insert_cols=[p[1] for p in pairs]))
    _assert_same_edges(g1, _from_scratch([p for p in pairs if p[0] != p[1]], n))


def test_mixed_delta_matches_from_scratch(base):
    rng = np.random.default_rng(0)
    ins = rng.integers(0, base.n, (9, 2))
    drop = rng.choice(base.nnz, 7, replace=False)
    delta = EdgeDelta(ins[:, 0], ins[:, 1], base.rows[drop], base.cols[drop])
    g1 = DynamicGraph(base).apply(delta)

    d = canonicalize(delta, base.n)
    keys = np.unique(base.rows.astype(np.int64) * base.n + base.cols)
    keys = np.setdiff1d(keys, d.delete_rows * base.n + d.delete_cols)
    keys = np.union1d(keys, d.insert_rows * base.n + d.insert_cols)
    want_pairs = np.stack([keys // base.n, keys % base.n], 1)
    _assert_same_edges(g1, _from_scratch(want_pairs, base.n))


def test_disconnecting_delta(base):
    """Deleting every edge incident to one vertex detaches it; the
    snapshot equals from-scratch construction minus that star, and
    incremental CC repairs the split exactly."""
    v = int(base.rows[np.argmax(np.bincount(base.rows))])  # wait: a hub
    inc = np.nonzero((base.rows == v) | (base.cols == v))[0]
    delta = EdgeDelta(delete_rows=base.rows[inc], delete_cols=base.cols[inc])
    g1 = DynamicGraph(base).apply(delta)
    assert not ((g1.rows == v).any() or (g1.cols == v).any())
    keep = np.nonzero(~((base.rows == v) | (base.cols == v)))[0]
    _assert_same_edges(
        g1, _from_scratch(np.stack([base.rows[keep], base.cols[keep]], 1),
                          base.n))

    e0 = build_engine(base, MIN_TIMES)
    e1 = build_engine(g1, MIN_TIMES)
    old = np.asarray(connected_components(e0).labels)
    got = cc_incremental(e1, old, canonicalize(delta, base.n))
    np.testing.assert_array_equal(np.asarray(got.labels),
                                  cc_reference(g1.rows, g1.cols, g1.n))


def test_canonicalize_rejects_out_of_range(base):
    with pytest.raises(ValueError):
        canonicalize(EdgeDelta(insert_rows=[0], insert_cols=[base.n]), base.n)
    with pytest.raises(ValueError):
        canonicalize(EdgeDelta(delete_rows=[-1], delete_cols=[0]), base.n)


def test_edge_diff_roundtrip(base):
    rng = np.random.default_rng(4)
    ins = rng.integers(0, base.n, (6, 2))
    drop = rng.choice(base.nnz, 5, replace=False)
    g1 = DynamicGraph(base).apply(
        EdgeDelta(ins[:, 0], ins[:, 1], base.rows[drop], base.cols[drop]))
    eff = edge_diff(base.rows, base.cols, g1.rows, g1.cols, base.n)
    r2, c2 = apply_edge_delta(base.rows, base.cols, base.n, eff)
    np.testing.assert_array_equal(r2, g1.rows)
    np.testing.assert_array_equal(c2, g1.cols)
    # touched endpoints are exactly the effective edges' endpoints
    t = touched_vertices(eff)
    want = np.unique(np.concatenate([eff.insert_rows, eff.insert_cols,
                                     eff.delete_rows, eff.delete_cols]))
    np.testing.assert_array_equal(t, want)


def test_content_keyed_weights_stable_across_snapshots(base):
    """The weight of a surviving edge must not depend on which other
    edges exist — the property incremental SSSP and mutate() rely on."""
    rng = np.random.default_rng(1)
    ins = rng.integers(0, base.n, (5, 2))
    g1 = DynamicGraph(base).apply(EdgeDelta(ins[:, 0], ins[:, 1]))
    w0 = content_keyed_weights(base.rows, base.cols, seed=5)
    w1 = content_keyed_weights(g1.rows, g1.cols, seed=5)
    k0 = base.rows.astype(np.int64) * base.n + base.cols
    k1 = g1.rows.astype(np.int64) * g1.n + g1.cols
    m0 = dict(zip(k0.tolist(), w0.tolist()))
    for k, w in zip(k1.tolist(), w1.tolist()):
        if k in m0:
            assert m0[k] == w
    assert content_keyed_weights(base.rows, base.cols, seed=6).tolist() \
        != w0.tolist()


# ---------------------------------------------------------------------------
# Incremental recompute == cold recompute
# ---------------------------------------------------------------------------

def _snapshots(base, kind):
    rng = np.random.default_rng(8)
    if kind == "grow":
        ins = rng.integers(0, base.n, (8, 2))
        delta = EdgeDelta(insert_rows=ins[:, 0], insert_cols=ins[:, 1])
    elif kind == "churn":
        ins = rng.integers(0, base.n, (8, 2))
        drop = rng.choice(base.nnz, 6, replace=False)
        delta = EdgeDelta(ins[:, 0], ins[:, 1],
                          base.rows[drop], base.cols[drop])
    else:                                   # shrink: delete only
        drop = rng.choice(base.nnz, 10, replace=False)
        delta = EdgeDelta(delete_rows=base.rows[drop],
                          delete_cols=base.cols[drop])
    g1 = DynamicGraph(base).apply(delta)
    return g1, canonicalize(delta, base.n)


@pytest.mark.parametrize("kind", ["grow", "churn", "shrink"])
def test_bfs_sssp_incremental_exact(base, kind):
    g1, d = _snapshots(base, kind)
    rng = np.random.default_rng(2)
    srcs = [int(s) for s in rng.integers(0, base.n, 3)]

    old_lv = np.asarray(
        bfs_multi(build_engine(base, BOOL_OR_AND), srcs).levels)
    e1_unit = build_engine(g1, MIN_PLUS, weighted=False)
    repair = plan_repair(e1_unit, d)
    inc = bfs_incremental(e1_unit, srcs, old_lv, d, repair=repair)
    cold = bfs_multi(build_engine(g1, BOOL_OR_AND), srcs)
    np.testing.assert_array_equal(inc.values, np.asarray(cold.levels))
    assert inc.values.dtype == np.int32

    e0_w = build_engine(base, MIN_PLUS, weighted=True, seed=5,
                        content_keyed=True)
    e1_w = build_engine(g1, MIN_PLUS, weighted=True, seed=5,
                        content_keyed=True)
    old_d = np.asarray(sssp_multi(e0_w, srcs).dist)
    inc_w = sssp_incremental(e1_w, srcs, old_d, d, repair=repair)
    cold_w = sssp_multi(e1_w, srcs)
    np.testing.assert_array_equal(inc_w.values, np.asarray(cold_w.dist))
    assert inc_w.traffic > 0 or d.n_inserts + d.n_deletes == 0
    assert traffic_of(cold_w) > 0


@pytest.mark.parametrize("kind", ["grow", "churn", "shrink"])
def test_cc_incremental_exact(base, kind):
    g1, d = _snapshots(base, kind)
    old = np.asarray(connected_components(build_engine(base,
                                                       MIN_TIMES)).labels)
    e1 = build_engine(g1, MIN_TIMES)
    inc = cc_incremental(e1, old, d)
    cold = connected_components(e1)
    np.testing.assert_array_equal(np.asarray(inc.labels),
                                  np.asarray(cold.labels))
    assert int(inc.n_components) == int(cold.n_components)
    np.testing.assert_array_equal(np.asarray(cold.labels),
                                  cc_reference(g1.rows, g1.cols, g1.n))


def test_empty_delta_incremental_is_free(base):
    """A no-op delta must keep every old answer and touch ~nothing: the
    relax sees an all-inf frontier and stops immediately."""
    d = canonicalize(EdgeDelta(), base.n)
    srcs = [1, 5]
    e_unit = build_engine(base, MIN_PLUS, weighted=False)
    old_lv = np.asarray(
        bfs_multi(build_engine(base, BOOL_OR_AND), srcs).levels)
    inc = bfs_incremental(e_unit, srcs, old_lv, d)
    np.testing.assert_array_equal(inc.values, old_lv)
    assert inc.traffic == 0.0 and inc.repair.traffic == 0.0


def test_pagerank_warm_same_fixpoint(base):
    g1, _d = _snapshots(base, "grow")
    e0 = build_engine(base, PLUS_TIMES, normalize=True)
    e1 = build_engine(g1, PLUS_TIMES, normalize=True)
    old = np.asarray(pagerank(e0, max_iters=200).rank)
    cold = pagerank(e1, max_iters=200)
    warm = pagerank_warm(e1, old, max_iters=200)
    assert float(warm.residual) <= 1e-6 and float(cold.residual) <= 1e-6
    np.testing.assert_allclose(np.asarray(warm.rank), np.asarray(cold.rank),
                               rtol=1e-4, atol=1e-7)
    assert int(warm.iterations) <= int(cold.iterations)


def test_relax_multi_cold_seed_equals_sssp_multi(base):
    """Seeding the warm-start runner with the cold-start state must be
    bit-identical to sssp_multi — same loop, same ops."""
    eng = build_engine(base, MIN_PLUS, weighted=True, seed=5,
                       content_keyed=True)
    srcs = [3, 11, 42]
    d0 = np.full((3, base.n), np.inf, np.float32)
    d0[np.arange(3), srcs] = 0.0
    got = relax_multi(eng, d0, d0.copy())
    want = sssp_multi(eng, srcs)
    np.testing.assert_array_equal(np.asarray(got.dist),
                                  np.asarray(want.dist))
    np.testing.assert_array_equal(np.asarray(got.iterations),
                                  np.asarray(want.iterations))
    np.testing.assert_array_equal(np.asarray(got.spmv_trips),
                                  np.asarray(want.spmv_trips))
    np.testing.assert_array_equal(np.asarray(got.consumed),
                                  np.asarray(want.consumed))


# ---------------------------------------------------------------------------
# Incremental partition-plan repair
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("balance", ["rows", "nnz"])
def test_plan_apply_delta_matches_fresh_count(base, balance):
    """Patching tile_nnz through the delta must agree with recounting the
    new edge list under the same cuts — for both balance modes and a 2D
    grid (permuted axes included)."""
    g1, d = _snapshots(base, "churn")
    n_pad = -(-base.n // 64) * 64
    # transposed adjacency, like every engine-facing plan
    plan = plan_partition(base.cols.astype(np.int64),
                          base.rows.astype(np.int64),
                          (n_pad, n_pad), (2, 4), balance)
    patched = plan.apply_delta(d.insert_cols, d.insert_rows,
                               d.delete_cols, d.delete_rows)
    fresh = np.bincount(plan.tiles_of(g1.cols.astype(np.int64),
                                      g1.rows.astype(np.int64)),
                        minlength=plan.n_devices)
    np.testing.assert_array_equal(np.asarray(patched.tile_nnz), fresh)
    # cuts unchanged: only the book-keeping moved
    assert patched.row_starts == plan.row_starts
    assert patched.col_starts == plan.col_starts


def test_plan_apply_delta_rejects_uncounted_delete(base):
    n_pad = -(-base.n // 64) * 64
    plan = plan_partition(base.cols.astype(np.int64),
                          base.rows.astype(np.int64),
                          (n_pad, n_pad), (8, 1), "nnz")
    absent = EdgeDelta(delete_rows=np.zeros(plan.n_devices * 64, np.int64),
                       delete_cols=np.arange(1, plan.n_devices * 64 + 1))
    with pytest.raises(AssertionError):
        plan.apply_delta(np.zeros(0, np.int64), np.zeros(0, np.int64),
                         absent.delete_rows, absent.delete_cols)


def test_repair_choice_patches_then_replans(base):
    from repro.graphs.cost_model import plan_for_graph, repair_choice

    choice = plan_for_graph(base, n_devices=8)
    small = canonicalize(
        EdgeDelta(insert_rows=[0, 1], insert_cols=[2, 3]), base.n)
    # drop the edges that are already present (effective delta only)
    eff = edge_diff(base.rows, base.cols,
                    *apply_edge_delta(base.rows, base.cols, base.n, small),
                    base.n)
    g_small = DynamicGraph(base).apply(eff)
    patched, replanned = repair_choice(choice, g_small, eff, n_devices=8)
    assert not replanned
    assert patched.strategy == choice.strategy
    assert sum(patched.plan.tile_nnz) == g_small.nnz
    assert (choice.strategy, choice.balance) in patched.costs

    # a hub-bomb delta: every remaining vertex points at vertex 0 —
    # one row band of the transposed plan balloons, imbalance drifts
    rows = np.arange(1, base.n, dtype=np.int64)
    bomb = EdgeDelta(insert_rows=np.zeros_like(rows), insert_cols=rows)
    g_bomb = DynamicGraph(base).apply(bomb)
    eff_bomb = edge_diff(base.rows, base.cols, g_bomb.rows, g_bomb.cols,
                         base.n)
    repaired, replanned = repair_choice(choice, g_bomb, eff_bomb,
                                        n_devices=8, max_imbalance=1.2)
    assert replanned
    assert sum(repaired.plan.tile_nnz) == g_bomb.nnz
    assert repaired.plan.imbalance() \
        <= choice.plan.apply_delta(eff_bomb.insert_cols,
                                   eff_bomb.insert_rows,
                                   eff_bomb.delete_cols,
                                   eff_bomb.delete_rows).imbalance() + 1e-9

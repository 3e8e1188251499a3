"""PartitionPlan + partition/unpartition: cut balance, layout round-trips,
and the ISSUE-4 edge cases (empty rows, single-device grids, star-graph
hubs, plan round-trip identity across every generator family)."""
import numpy as np
import pytest

from repro.core.partition import (
    balanced_cuts, partition, plan_partition, unpartition,
)
from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
from repro.graphs.datasets import rmat_graph, road_graph, uniform_graph

GRIDS = [(8, 1), (1, 8), (2, 4), (1, 1)]


def _family_graph(family: str):
    if family == "road":
        return road_graph(900, 2.6, seed=3)
    if family == "uniform":
        return uniform_graph(800, 3200, seed=3)
    return rmat_graph(1024, 8000, skew=0.6, seed=3)


def _edges(g, sr, seed=0):
    rng = np.random.default_rng(seed)
    rows, cols = g.cols.astype(np.int64), g.rows.astype(np.int64)
    if sr.name == "bool_or_and":
        vals = np.ones(rows.shape[0], np.int32)
    else:
        vals = rng.integers(1, 9, rows.shape[0]).astype(np.float32)
    return rows, cols, vals


# ---------------------------------------------------------------------------
# balanced_cuts
# ---------------------------------------------------------------------------

def test_balanced_cuts_covers_and_balances():
    rng = np.random.default_rng(0)
    w = rng.integers(0, 50, 1000)
    cuts = balanced_cuts(w, 8)
    assert cuts[0] == 0 and cuts[-1] == 1000
    assert (np.diff(cuts) >= 0).all()
    shares = np.add.reduceat(w, cuts[:-1])[:8]
    ideal = w.sum() / 8
    assert shares.max() <= ideal + w.max()   # off by at most one element


def test_balanced_cuts_zero_weights_fall_back_to_equal_count():
    cuts = balanced_cuts(np.zeros(64, np.int64), 8)
    np.testing.assert_array_equal(np.diff(cuts), [8] * 8)


def test_balanced_cuts_single_part():
    np.testing.assert_array_equal(balanced_cuts(np.ones(10, np.int64), 1),
                                  [0, 10])


# ---------------------------------------------------------------------------
# plan round-trip: partition → unpartition is the identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["road", "uniform", "rmat"])
@pytest.mark.parametrize("balance", ["rows", "nnz"])
@pytest.mark.parametrize("grid,fmt", [((8, 1), "csr"), ((1, 8), "csc"),
                                      ((2, 4), "coo")])
def test_partition_unpartition_identity(family, balance, grid, fmt):
    g = _family_graph(family)
    sr = PLUS_TIMES
    rows, cols, vals = _edges(g, sr)
    pm = partition(rows, cols, vals, (g.n, g.n), grid, fmt, sr,
                   balance=balance)
    r2, c2, v2 = unpartition(pm, sr)
    order = np.lexsort((cols, rows))
    np.testing.assert_array_equal(r2, rows[order])
    np.testing.assert_array_equal(c2, cols[order])
    np.testing.assert_array_equal(v2, vals[order])
    assert sum(pm.plan.tile_nnz) == rows.shape[0]
    if fmt == "csr":  # one static row bound for the stacked tiles
        longest = np.diff(np.asarray(pm.parts.row_ptr), axis=1).max()
        assert pm.parts.max_row_nnz == max(1, longest)


def test_partition_unpartition_identity_bsr():
    g = _family_graph("uniform")
    sr = PLUS_TIMES
    rows, cols, vals = _edges(g, sr)
    pm = partition(rows, cols, vals, (g.n, g.n), (2, 4), "bsr", sr,
                   block=(16, 16), balance="nnz")
    r2, c2, v2 = unpartition(pm, sr)
    order = np.lexsort((cols, rows))
    np.testing.assert_array_equal(r2, rows[order])
    np.testing.assert_array_equal(c2, cols[order])
    np.testing.assert_array_equal(v2, vals[order])


# ---------------------------------------------------------------------------
# edge cases: empty rows / empty graph / single device / star hub
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("balance", ["rows", "nnz"])
def test_empty_graph_partitions(balance):
    sr = BOOL_OR_AND
    empty = np.zeros(0, np.int64)
    pm = partition(empty, empty, np.zeros(0, np.int32), (64, 64), (2, 4),
                   "coo", sr, balance=balance)
    assert pm.plan.imbalance() == 1.0
    r2, c2, _ = unpartition(pm, sr)
    assert r2.shape[0] == 0 and c2.shape[0] == 0
    x = np.arange(64)
    xs = pm.plan.shard_input_vector(x, 0)
    assert xs.shape == (8, pm.plan.in_per)


@pytest.mark.parametrize("balance", ["rows", "nnz"])
def test_rows_without_nnz_are_planned(balance):
    """A matrix whose top half is empty: every edge lives in rows >= 32.
    nnz balancing must still cover the whole index space and keep the
    round-trip exact."""
    sr = PLUS_TIMES
    rng = np.random.default_rng(1)
    rows = rng.integers(32, 64, 300).astype(np.int64)
    cols = rng.integers(0, 64, 300).astype(np.int64)
    keys = np.unique(rows * 64 + cols)
    rows, cols = keys // 64, keys % 64
    vals = rng.integers(1, 9, rows.shape[0]).astype(np.float32)
    pm = partition(rows, cols, vals, (64, 64), (8, 1), "csr", sr,
                   balance=balance)
    assert pm.plan.row_starts[0] == 0 and pm.plan.row_starts[-1] == 64
    r2, c2, v2 = unpartition(pm, sr)
    order = np.lexsort((cols, rows))
    np.testing.assert_array_equal(r2, rows[order])
    np.testing.assert_array_equal(c2, cols[order])
    np.testing.assert_array_equal(v2, vals[order])


@pytest.mark.parametrize("balance", ["rows", "nnz"])
def test_single_device_grid(balance):
    g = _family_graph("rmat")
    sr = MIN_PLUS
    rows, cols, vals = _edges(g, sr)
    pm = partition(rows, cols, vals, (g.n, g.n), (1, 1), "csr", sr,
                   balance=balance)
    assert pm.plan.n_devices == 1 and pm.plan.imbalance() == 1.0
    x = np.random.default_rng(0).random(g.n).astype(np.float32)
    np.testing.assert_array_equal(
        pm.plan.unshard_output_vector(pm.plan.shard_output_vector(x, np.inf)),
        x)
    r2, _, _ = unpartition(pm, sr)
    assert r2.shape[0] == rows.shape[0]


def test_star_graph_nnz_balance():
    """One hub row holding half the nnz: the prefix-sum cut isolates the
    hub, neighbours share the rest, and the split stays exact — the
    imbalance is bounded by the hub's own share (no split can do better
    without breaking rows)."""
    n = 256
    hub = np.zeros(n - 1, np.int64)
    leaves = np.arange(1, n, dtype=np.int64)
    rows = np.concatenate([hub, leaves])        # hub→leaf and leaf→hub
    cols = np.concatenate([leaves, hub])
    vals = np.ones(rows.shape[0], np.float32)
    sr = PLUS_TIMES
    plan = plan_partition(rows, cols, (n, n), (8, 1), "nnz")
    total = sum(plan.tile_nnz)
    assert total == rows.shape[0]
    # the hub row sits alone in its band (neighbouring bands may be empty:
    # the hub already exceeds the equal share)
    hub_band = int(np.argmax(plan.tile_nnz))
    assert plan.tile_nnz[hub_band] == n - 1     # structural floor
    assert (plan.row_starts[hub_band + 1] - plan.row_starts[hub_band]) == 1
    # every other band holds only single-nnz leaf rows → near-ideal share
    others = [t for i, t in enumerate(plan.tile_nnz) if i != hub_band]
    assert max(others) <= total // 8 + 2
    pm = partition(rows, cols, vals, (n, n), (8, 1), "csr", sr, plan=plan)
    r2, c2, _ = unpartition(pm, sr)
    order = np.lexsort((cols, rows))
    np.testing.assert_array_equal(r2, rows[order])
    np.testing.assert_array_equal(c2, cols[order])


# ---------------------------------------------------------------------------
# layout helpers: shard/unshard are exact inverses
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("balance", ["rows", "nnz"])
@pytest.mark.parametrize("grid", GRIDS)
def test_output_layout_round_trip(balance, grid):
    g = _family_graph("rmat")
    rows, cols, _ = _edges(g, PLUS_TIMES)
    plan = plan_partition(rows, cols, (g.n, g.n), grid, balance)
    y = np.random.default_rng(2).random(g.n).astype(np.float32)
    ys = plan.shard_output_vector(y, 0.0)
    assert ys.shape == (plan.n_devices, plan.out_per)
    np.testing.assert_array_equal(plan.unshard_output_vector(ys), y)
    # batched + rows variants agree with the vector layout
    yb = np.stack([y, y[::-1]])
    sb = plan.shard_input_batch(yb, 0.0)
    for i in range(2):
        np.testing.assert_array_equal(sb[:, i],
                                      plan.shard_input_vector(yb[i], 0.0))
    mat = np.random.default_rng(3).random((g.n, 3)).astype(np.float32)
    np.testing.assert_array_equal(
        plan.unshard_output_rows(plan.shard_output_rows(mat, 0.0)), mat)


@pytest.mark.parametrize("grid", [(8, 1), (2, 4)])
def test_rows_balance_layout_is_plain_slicing(grid):
    """balance="rows" must keep the legacy canonical layout bit-for-bit:
    plain row-major uniform chunks (the pre-plan call sites relied on a
    bare reshape)."""
    g = _family_graph("uniform")
    rows, cols, _ = _edges(g, PLUS_TIMES)
    n_pad = -(-g.n // 64) * 64
    plan = plan_partition(rows, cols, (n_pad, n_pad), grid, "rows")
    x = np.arange(n_pad, dtype=np.float32)
    np.testing.assert_array_equal(plan.shard_input_vector(x, 0.0),
                                  x.reshape(8, -1))
    np.testing.assert_array_equal(plan.unshard_output_vector(x.reshape(8, -1)),
                                  x)


@pytest.mark.parametrize("family", ["road", "uniform", "rmat"])
def test_nnz_balance_beats_equal_rows_on_skew(family):
    g = _family_graph(family)
    rows, cols, _ = _edges(g, PLUS_TIMES)
    for grid in [(8, 1), (1, 8), (2, 4)]:
        eq = plan_partition(rows, cols, (g.n, g.n), grid, "rows").imbalance()
        bal = plan_partition(rows, cols, (g.n, g.n), grid, "nnz").imbalance()
        assert bal <= eq + 1e-9, (family, grid, eq, bal)
    if family == "rmat":
        assert plan_partition(rows, cols, (g.n, g.n), (8, 1),
                              "rows").imbalance() > 2.0
        for grid in [(8, 1), (1, 8), (2, 4)]:
            assert plan_partition(rows, cols, (g.n, g.n), grid,
                                  "nnz").imbalance() <= 1.15


def test_non_divisible_rows_plan_errors_loudly():
    """balance="rows" keeps the legacy caller-pads contract: a padded
    extent that does not divide by D must raise in the layout helpers (the
    old bare reshape errored too) instead of silently dropping trailing
    indices; balance="nnz" rounds itself divisible."""
    rng = np.random.default_rng(4)
    rows = rng.integers(0, 900, 500).astype(np.int64)
    cols = rng.integers(0, 900, 500).astype(np.int64)
    plan = plan_partition(rows, cols, (900, 900), (2, 4), "rows")
    with pytest.raises(ValueError):
        _ = plan.in_per
    with pytest.raises(ValueError):
        plan.shard_input_vector(np.zeros(900, np.float32), 0.0)
    with pytest.raises(ValueError):
        plan.unshard_output_vector(np.zeros((8, 113), np.float32))
    balanced = plan_partition(rows, cols, (900, 900), (2, 4), "nnz")
    x = rng.random(900).astype(np.float32)
    np.testing.assert_array_equal(
        balanced.unshard_output_vector(balanced.shard_output_vector(x, 0.0)),
        x)


def test_partition_rejects_bad_balance_and_mismatched_plan():
    g = _family_graph("uniform")
    rows, cols, vals = _edges(g, PLUS_TIMES)
    with pytest.raises(ValueError):
        plan_partition(rows, cols, (g.n, g.n), (8, 1), "degree")
    plan = plan_partition(rows, cols, (g.n, g.n), (8, 1), "nnz")
    with pytest.raises(AssertionError):
        partition(rows, cols, vals, (g.n, g.n), (2, 4), "csr", PLUS_TIMES,
                  plan=plan)

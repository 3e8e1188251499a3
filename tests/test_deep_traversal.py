"""Exact traversals on graphs deeper than 64 levels, served exactly as a
client drives the server (``AsyncGraphServer.add_tenant(..., batch_size=8,
weight_seed=s)``): BFS levels and SSSP distances equal the plain
references entry for entry at any depth, PPR keeps its stopping rule of
64 iterations or an L1 residual of 1e-6, the runner's loop carries no
array larger than ``[B, n]``, and the server's trip histogram and
counters agree with the payloads' ``iterations``."""
import jax
import numpy as np
import pytest
from scipy.sparse.csgraph import shortest_path
from scipy.spatial import cKDTree

from repro.core import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
from repro.graphs import (
    bfs, bfs_multi, generate, road_graph, sssp, sssp_multi, sssp_reference,
    trained_stump,
)
from repro.graphs.datasets import Graph, _symmetrize
from repro.graphs.engine import build_engine, edge_values
from repro.graphs.multi import make_bfs_multi, make_ppr_multi, make_sssp_multi
from repro.obs import trace
from repro.serve.graph_engine import AsyncGraphServer
from repro.serve.scheduler import FakeClock

B = 8
SEED = 2 ** 31 + 11


def _rgg(scale: int, seed: int) -> Graph:
    """A random geometric graph: 2**scale points uniform in the unit
    square, an edge where two lie within 0.55 * sqrt(ln n / n)."""
    n = 1 << scale
    points = np.random.default_rng(seed).random((n, 2))
    pairs = cKDTree(points).query_pairs(0.55 * np.sqrt(np.log(n) / n),
                                        output_type="ndarray")
    rows, cols = _symmetrize(pairs[:, 0], pairs[:, 1], n)
    return Graph(rows, cols, n, "rgg")


def _roots(g: Graph) -> list:
    """Two buckets of roots: the lattice's corners (94 hops apart) and
    seeded vertices of degree >= 1."""
    live = np.flatnonzero(g.out_degrees() > 0)
    rng = np.random.default_rng(5)
    picks = [int(v) for v in rng.choice(live, 2 * B, replace=False)]
    corners = [0, g.n - 1]
    return corners + [v for v in picks if v not in corners][: 2 * B - 2]


GRAPHS = {"lattice": lambda: road_graph(48 * 48, 4.0),
          "rgg": lambda: _rgg(14, 20260116)}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def deep(request):
    return request.param, GRAPHS[request.param]()


def _serve(g: Graph, alg: str, roots: list, **kwargs):
    """Answers of one tenant to ``roots``, drained through the async
    server, and the tenant's stats."""
    asrv = AsyncGraphServer(clock=FakeClock())
    asrv.add_tenant("g", g, batch_size=B, weight_seed=SEED, **kwargs)
    tickets = [asrv.submit("g", alg, r) for r in roots]
    asrv.drain()
    return [tk.wait(timeout=0) for tk in tickets], asrv.stats("g")


def _hops(g: Graph, roots: list) -> np.ndarray:
    from scipy.sparse import csr_matrix

    adj = csr_matrix((np.ones(g.nnz), (g.rows, g.cols)), shape=(g.n, g.n))
    d = shortest_path(adj, unweighted=True, indices=roots)
    return np.where(np.isinf(d), -1, d).astype(np.int64)


def test_bfs_deeper_than_64_levels_is_exact(deep):
    _name, g = deep
    roots = _roots(g)
    want = _hops(g, roots)
    assert want.max() > 64
    got, _st = _serve(g, "bfs", roots)
    for p, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(p["levels"]), w)
        # the loop's last trip finds the frontier empty
        assert p["iterations"] == w.max() + 1


def test_sssp_deeper_than_64_rounds_is_exact(deep):
    _name, g = deep
    roots = _roots(g)
    got, _st = _serve(g, "sssp", roots)
    w = edge_values(g, MIN_PLUS, weighted=True, seed=SEED,
                    content_keyed=True)
    rounds = [p["iterations"] for p in got]
    assert max(rounds) > 64
    for p, r in zip(got, roots):
        np.testing.assert_array_equal(
            np.asarray(p["dist"]),
            sssp_reference(g.rows, g.cols, w, g.n, r).astype(np.float32))


@pytest.mark.parametrize("alg", ["bfs", "sssp"])
def test_batched_rows_count_as_single_runs(deep, alg):
    """Past 64 levels, each batched row's trips, trips on the SpMV and
    frontier entries read equal the single-source run's."""
    _name, g = deep
    roots = _roots(g)[:B]
    if alg == "bfs":
        eng = build_engine(g, BOOL_OR_AND, trained_stump())
        res, single = bfs_multi(eng, roots), bfs
    else:
        eng = build_engine(g, MIN_PLUS, trained_stump(), weighted=True,
                           seed=5)
        res, single = sssp_multi(eng, roots), sssp
    for i in (0, 1):
        ref = single(eng, roots[i])
        for field in ("iterations", "spmv_trips", "consumed"):
            assert int(getattr(res, field)[i]) == int(getattr(ref, field))
    assert int(res.iterations[:2].max()) > 64


def test_ppr_keeps_its_stopping_rule(deep):
    """PPR alone keeps the server's 64 iterations: a row stops at an L1
    residual of 1e-6 or at 64 iterations, whichever comes first. On the
    lattice the residual is still over 1e-6 at 64, so the limit binds."""
    name, g = deep
    got, _st = _serve(g, "ppr", _roots(g)[:B])
    for p in got:
        assert p["iterations"] <= 64
        if p["iterations"] < 64:
            assert p["residual"] <= 1e-6
        if p["residual"] > 1e-6:
            assert p["iterations"] == 64
    if name == "lattice":
        assert max(p["residual"] for p in got) > 1e-6
    # a tenant may still set the limit
    fewer, _st = _serve(g, "ppr", _roots(g)[:B], max_iters=20)
    assert max(p["iterations"] for p in fewer) == 20


def _loop_carries(runner, *args) -> list:
    """Avals of every array a ``while`` loop of the runner carries from
    one trip to the next (loop constants, the graph among them, left
    out)."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "while":
                body = eqn.params["body_jaxpr"].jaxpr
                out.extend(v.aval for v in
                           body.invars[eqn.params["body_nconsts"]:])
            for param in eqn.params.values():
                for sub in (param if isinstance(param, (tuple, list))
                            else (param,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(runner.jitted)(runner.mats, *args).jaxpr)
    return out


@pytest.mark.parametrize("alg", ["bfs", "sssp", "ppr"])
def test_loop_state_is_bounded_by_the_block(deep, alg):
    """Whatever the bound on trips (``n_true`` for BFS and SSSP), the loop
    carries the [B, n] state and [B] counters, nothing that grows with
    the number of trips."""
    _name, g = deep
    make, sr, kw = {"bfs": (make_bfs_multi, BOOL_OR_AND, {}),
                    "sssp": (make_sssp_multi, MIN_PLUS, {"weighted": True}),
                    "ppr": (make_ppr_multi, PLUS_TIMES,
                            {"normalize": True})}[alg]
    eng = build_engine(g, sr, **kw)
    carries = _loop_carries(make(eng, B), np.zeros(B, np.int32))
    assert carries
    assert all(a.size <= B * eng.n and a.ndim <= 2 for a in carries)
    assert any(a.shape == (B, eng.n) for a in carries)
    assert all(a.shape in ((), (B,), (B, eng.n)) for a in carries)


@pytest.mark.parametrize("graph", ["rgg", "face"])
def test_trip_counters_agree_with_payloads(graph):
    """``bucket_trips`` and each ``serve/bucket_compute`` span's ``trips``
    hold each bucket's deepest row, and the per-row trip counters on the
    SpMSpV ladder and on the SpMV add up to the payloads' ``iterations``,
    split as the runner split them."""
    g = _rgg(12, 7) if graph == "rgg" else generate("face", scale=0.15,
                                                    seed=1)
    roots = _roots(g)
    with trace.tracing() as t:
        got, st = _serve(g, "bfs", roots)
    lat = st["latency"]
    buckets = [got[lo: lo + B] for lo in range(0, len(got), B)]
    trips = [max(p["iterations"] for p in b) for b in buckets]
    h = lat["bucket_trips"]
    assert h["count"] == st["batches"] == len(buckets)
    assert h["max"] == max(trips) and h["min"] == min(trips)
    assert h["mean"] * h["count"] == pytest.approx(sum(trips))
    assert [s.attrs["trips"] for s in t.filter("serve/bucket_compute")] \
        == trips
    rows = sum(p["iterations"] for p in got)
    assert lat["row_trips_spmv"] + lat["row_trips_spmspv"] == rows
    eng = build_engine(g, BOOL_OR_AND, trained_stump())   # the server's
    direct = [bfs_multi(eng, roots[lo: lo + B])
              for lo in range(0, len(roots), B)]
    assert lat["row_trips_spmv"] == sum(int(np.sum(r.spmv_trips))
                                        for r in direct)
    assert [p["iterations"] for p in got] == [
        int(i) for r in direct for i in np.asarray(r.iterations)]
    if graph == "face":      # scale-free: the frontier densifies
        assert lat["row_trips_spmv"] > 0 and lat["row_trips_spmspv"] > 0

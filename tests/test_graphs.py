"""Graph applications vs classical oracles; dataset generator fidelity;
decision-tree cost model behaviour (paper §4.2, §5.3, §6)."""
import numpy as np
import pytest

from repro.core import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
from repro.graphs import (
    TABLE2, bfs, bfs_reference, generate, ppr, ppr_reference, sssp,
    sssp_reference,
)
from repro.graphs.cost_model import trained_stump
from repro.graphs.engine import build_engine, edge_values

POLICIES = ["spmv", "spmspv", "adaptive"]


@pytest.fixture(scope="module")
def small_graph():
    g = generate("face", scale=0.15, seed=1)
    src = int(np.argmax(g.out_degrees()))
    return g, src


@pytest.fixture(scope="module")
def stump():
    return trained_stump()


@pytest.mark.parametrize("policy", POLICIES)
def test_bfs_matches_reference(small_graph, stump, policy):
    g, src = small_graph
    eng = build_engine(g, BOOL_OR_AND, stump)
    res = bfs(eng, src, policy=policy)
    ref = bfs_reference(g.rows, g.cols, g.n, src)
    np.testing.assert_array_equal(np.asarray(res.levels), ref)


@pytest.mark.parametrize("policy", POLICIES)
def test_sssp_matches_dijkstra(small_graph, stump, policy):
    g, src = small_graph
    eng = build_engine(g, MIN_PLUS, stump, weighted=True, seed=5)
    w = edge_values(g, MIN_PLUS, weighted=True, seed=5)
    ref = sssp_reference(g.rows, g.cols, w, g.n, src)
    res = sssp(eng, src, policy=policy)
    np.testing.assert_allclose(np.asarray(res.dist), ref, rtol=1e-5)


@pytest.mark.parametrize("policy", POLICIES)
def test_ppr_matches_power_iteration(small_graph, stump, policy):
    g, src = small_graph
    eng = build_engine(g, PLUS_TIMES, stump, normalize=True)
    res = ppr(eng, src, policy=policy)
    ref = ppr_reference(g.rows, g.cols, g.n, src)
    np.testing.assert_allclose(np.asarray(res.rank), ref, rtol=1e-3, atol=1e-6)


def test_bfs_adaptive_switches_kernel(small_graph, stump):
    """Scale-free graph → frontier densifies past 50% → SpMV must kick in,
    and early sparse levels must use SpMSpV (paper Fig 4 behaviour)."""
    g, src = small_graph
    eng = build_engine(g, BOOL_OR_AND, stump)
    assert eng.graph_class == "scale_free"
    res = bfs(eng, src, policy="adaptive")
    # trip t reads the vertices at level t; the last trip finds none new
    ref = bfs_reference(g.rows, g.cols, g.n, src)
    sizes = np.bincount(ref[ref >= 0])
    dens = sizes.astype(np.float32) / np.float32(g.n)
    assert int(res.iterations) == len(sizes)
    assert int(res.consumed) == int(sizes.sum())
    assert int(res.spmv_trips) == int(np.sum(dens > eng.threshold))
    assert dens[0] <= eng.threshold, "first (sparsest) level must be SpMSpV"
    assert 0 < int(res.spmv_trips) < int(res.iterations)


def test_bfs_on_bsr_kernels(stump):
    """End-to-end BFS through the Pallas (interpret) tile kernels."""
    g = generate("ca-Q", scale=0.12, seed=2)
    src = int(np.argmax(g.out_degrees()))
    eng = build_engine(g, BOOL_OR_AND, stump, fmt_spmv="bsr", fmt_spmspv="bsr")
    res = bfs(eng, src, policy="adaptive")
    ref = bfs_reference(g.rows, g.cols, g.n, src)
    np.testing.assert_array_equal(np.asarray(res.levels), ref)


# ------------------------- dataset generators -----------------------------

@pytest.mark.parametrize("abbrev", ["r-TX", "face", "g-18", "A302", "as00"])
def test_generator_matches_table2_stats(abbrev):
    spec = TABLE2[abbrev]
    g = generate(abbrev, scale=0.05 if spec.nodes > 50000 else 0.5, seed=0)
    f = g.features()
    assert abs(f.avg_degree - spec.avg_deg) / spec.avg_deg < 0.45, (f, spec)
    # degree-variance *class* must match: regular graphs keep cv ≲ 1,
    # scale-free cv ≳ 1 (exact tails are size-dependent)
    cv_target = spec.deg_std / spec.avg_deg
    cv_got = f.degree_std / max(f.avg_degree, 1e-9)
    if cv_target < 0.9:
        assert cv_got < 1.2, (f, spec)
    else:
        assert cv_got > 0.7, (f, spec)


def test_cost_model_recovers_paper_classes(stump):
    """The trained stump must assign the paper's classes (§4.2.1): road →
    regular/20%, social+web+graph500 → scale-free/50%."""
    for abbrev, expected in [("r-TX", "regular"), ("face", "scale_free"),
                             ("g-18", "scale_free"), ("s-S11", "scale_free")]:
        spec = TABLE2[abbrev]
        g = generate(abbrev, scale=0.05, seed=3)
        assert stump.classify(g.features()) == expected, abbrev
        thr = stump.switch_threshold(g.features())
        assert thr == (0.2 if expected == "regular" else 0.5)


def test_pagerank_matches_power_iteration(small_graph, stump):
    """Global PageRank (uniform teleport): dense from step 0 — the SpMV
    end of the paper's density spectrum."""
    from repro.core import PLUS_TIMES
    from repro.graphs import pagerank, pagerank_reference
    g, _src = small_graph
    eng = build_engine(g, PLUS_TIMES, stump, normalize=True)
    res = pagerank(eng)
    ref = pagerank_reference(g.rows, g.cols, g.n)
    np.testing.assert_allclose(np.asarray(res.rank), ref, rtol=1e-3, atol=1e-6)
    # dense iterate -> SpMV throughout
    assert int(res.spmv_trips) == int(res.iterations) > 0
    assert int(res.consumed) == g.n * int(res.iterations)

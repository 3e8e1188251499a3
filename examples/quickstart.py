"""Quickstart: ALPHA-PIM's linear-algebraic graph engine in ~40 lines.

Generates a Table-2 stand-in graph, builds the adaptive semiring engine and
runs BFS / SSSP / PPR — printing per-level frontier density and which kernel
(SpMSpV vs SpMV) the paper's §4.2 decision-tree policy picked.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
from repro.graphs import bfs, ppr, sssp
from repro.graphs.cost_model import trained_stump
from repro.graphs.datasets import generate, largest_component_source
from repro.graphs.engine import build_engine


def main():
    g = generate("face", scale=0.4, seed=0)   # facebook_combined stand-in
    src = largest_component_source(g)
    stump = trained_stump()
    print(f"graph: n={g.n} nnz={g.nnz} avg_deg={g.features().avg_degree:.1f} "
          f"class={stump.classify(g.features())} "
          f"switch@{stump.switch_threshold(g.features()):.0%} density")

    eng = build_engine(g, BOOL_OR_AND, stump)
    res = bfs(eng, src, policy="adaptive")
    print(f"\nBFS from {src}: {int(res.iterations)} levels, "
          f"{int((np.asarray(res.levels) >= 0).sum())}/{g.n} reached")
    print(f"  {int(res.spmv_trips)} of {int(res.iterations)} trips on the "
          f"SpMV, the rest on the SpMSpV")
    levels = np.asarray(res.levels)
    for it, size in enumerate(np.bincount(levels[levels >= 0])):
        d = size / g.n
        k = "SpMV  " if d > eng.threshold else "SpMSpV"
        print(f"  level {it:2d}: density={d:6.1%}  kernel={k}")

    eng = build_engine(g, MIN_PLUS, stump, weighted=True)
    res = sssp(eng, src, policy="adaptive")
    dist = np.asarray(res.dist)
    print(f"\nSSSP: {int(res.iterations)} rounds, "
          f"mean finite distance={dist[np.isfinite(dist)].mean():.2f}")

    eng = build_engine(g, PLUS_TIMES, stump, normalize=True)
    res = ppr(eng, src, policy="adaptive")
    top = np.argsort(-np.asarray(res.rank))[:5]
    print(f"\nPPR({src}): top-5 nodes {top.tolist()}, "
          f"{int(res.iterations)} iterations")


if __name__ == "__main__":
    main()

"""The graphs of the benchmark's deployments, in vectorised numpy.

These are the benchmark's own copies: they import nothing of the program,
so a change to the program cannot change the graphs it is measured on.

A configuration's ``generator`` names its graph family, the module
``bench/generators/<generator>.py`` whose ``tuples(cfg, rng)`` draws the
edge tuples (for example ``kronecker``, Graph500's generator, and
``urand``, GAP's ``-u``). Every family's tuples then become the graph as
Graph500's kernel 1 and GAP's builder build it: undirected (each edge
stored in both directions), self-loops and duplicate edges dropped.

Relabelling: Graph500 relabels vertices by a random permutation. Here the
edge tuples are drawn from the configuration's fixed ``structure_seed`` and
the permutation (and the stored edge order) from the run's seed. Every
seed therefore serves an isomorphic graph: the same vertex and edge
counts, the same degree sequence and the same array shapes (so every
compiled program is found in the compile cache), with hubs and neighbour
lists at seed-dependent positions in memory.
"""
from __future__ import annotations

import numpy as np

from bench import spec


def _undirected_simple(src: np.ndarray, dst: np.ndarray, n: int):
    """Both directions of every edge, self-loops and duplicates dropped;
    returns (rows, cols) sorted by (row, col)."""
    keep = src != dst
    src, dst = src[keep], dst[keep]
    keys = np.concatenate([src * n + dst, dst * n + src])
    keys = np.unique(keys)
    return keys // n, keys % n


def structure(cfg: dict):
    """The configuration's edge set before relabelling: (rows, cols, n)."""
    rng = np.random.default_rng(int(cfg["structure_seed"]))
    src, dst, n = spec.generator(cfg["generator"]).tuples(cfg, rng)
    rows, cols = _undirected_simple(src, dst, n)
    return rows, cols, n


def generate(cfg: dict, seed: int):
    """The graph one run serves: the configuration's structure under a
    vertex permutation drawn from ``seed``, its directed entries stored in
    a seed-drawn order. Returns int32 (rows, cols), n and the permutation
    (structural vertex v is served as ``perm[v]``)."""
    rows, cols, n = structure(cfg)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    order = rng.permutation(rows.shape[0])
    return (perm[rows][order].astype(np.int32),
            perm[cols][order].astype(np.int32), n, perm)

"""Graph generators of the benchmark's deployments, in vectorised numpy.

These are the benchmark's own copies: they import nothing of the program,
so a change to the program cannot change the graphs it is measured on.

* ``kronecker`` follows the Graph500 reference generator
  (``kronecker_generator.m``, specification section 3): ``edgefactor * 2**S``
  edge tuples, each of its ``S`` bit levels drawn with the initiator
  probabilities A, B, C (D = 1 - A - B - C).
* ``urand`` follows the GAP Benchmark Suite's ``-u`` generator: the same
  number of tuples with both endpoints uniform over the vertices.

Both then build the graph as Graph500's kernel 1 and GAP's builder do:
undirected (each edge stored in both directions), self-loops and duplicate
edges dropped.

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


def _kronecker_tuples(scale: int, edgefactor: int, a: float, b: float,
                      c: float, rng: np.random.Generator):
    """Graph500 ``kronecker_generator`` bit loop, without its permutations."""
    m = edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for level in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << level
        dst |= jj.astype(np.int64) << level
    return src, dst


def _urand_tuples(scale: int, edgefactor: int, rng: np.random.Generator):
    n, m = 1 << scale, edgefactor << scale
    return rng.integers(0, n, m), rng.integers(0, n, m)


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
    scale, ef = int(cfg["scale"]), int(cfg["edgefactor"])
    rng = np.random.default_rng(int(cfg["structure_seed"]))
    if cfg["generator"] == "kronecker":
        a, b, c = (float(cfg["initiator"][k]) for k in ("A", "B", "C"))
        src, dst = _kronecker_tuples(scale, ef, a, b, c, rng)
    elif cfg["generator"] == "urand":
        src, dst = _urand_tuples(scale, ef, rng)
    else:
        raise ValueError(f"unknown generator {cfg['generator']!r}")
    n = 1 << scale
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

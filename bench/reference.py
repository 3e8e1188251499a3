"""The plain reference for both graph configurations, and the comparison
that decides ``correct``.

It imports nothing of the program. BFS levels come from scipy's
unweighted shortest paths, SSSP distances from scipy's Dijkstra, both over
the directed entries ``rows[i] -> cols[i]`` of the generated graph. The SSSP
edge weights are the served path's integer weights 1..9, hashed from an
edge's endpoints and the weight seed; :func:`edge_weights` is this
benchmark's own copy of that hash.

The configurations guarantee exact answers: every vertex's hop count
(``-1`` where unreachable) and every vertex's shortest distance (``inf``
where unreachable). A comparison counts the entries that differ, and its
limit is 0.

The control breaks that guarantee the way an early-stopping traversal
would: it is the reference with the last level of each answer left
unreached (:func:`truncate_last_level`). It must fail the comparison.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra, shortest_path

PAYLOAD_FIELD = {"bfs": "levels", "sssp": "dist"}


def edge_weights(rows: np.ndarray, cols: np.ndarray,
                 seed: int) -> np.ndarray:
    """Per-edge weights in {1..9} from a splitmix-style hash of the edge's
    endpoints and ``seed``."""
    seed_mix = np.uint64((seed * 0xD6E8FEB86659FD93) % (1 << 64))
    h = (np.asarray(rows, np.uint64) * np.uint64(0x9E3779B97F4A7C15)
         ^ np.asarray(cols, np.uint64) * np.uint64(0xC2B2AE3D27D4EB4F)
         ^ seed_mix)
    h ^= h >> np.uint64(33)
    h *= np.uint64(0xFF51AFD7ED558CCD)
    h ^= h >> np.uint64(29)
    return (1 + (h % np.uint64(9))).astype(np.float64)


def _adjacency(rows, cols, n, data) -> sp.csr_matrix:
    return sp.csr_matrix((data, (rows, cols)), shape=(n, n))


def bfs_levels(rows, cols, n, roots) -> np.ndarray:
    """[len(roots), n] hop counts along rows -> cols; -1 where unreachable."""
    adj = _adjacency(rows, cols, n, np.ones(rows.shape[0]))
    d = shortest_path(adj, method="D", directed=True, unweighted=True,
                      indices=np.asarray(roots))
    return np.where(np.isinf(d), -1, d).astype(np.int64)


def sssp_distances(rows, cols, n, roots, weight_seed: int) -> np.ndarray:
    """[len(roots), n] shortest distances; inf where unreachable."""
    adj = _adjacency(rows, cols, n, edge_weights(rows, cols, weight_seed))
    return dijkstra(adj, directed=True, indices=np.asarray(roots))


def answers(alg: str, rows, cols, n, roots, weight_seed: int) -> np.ndarray:
    if alg == "bfs":
        return bfs_levels(rows, cols, n, roots)
    if alg == "sssp":
        return sssp_distances(rows, cols, n, roots, weight_seed)
    raise ValueError(f"no reference for {alg!r}")


def truncate_last_level(alg: str, ref_rows: np.ndarray,
                        bfs_rows: np.ndarray) -> np.ndarray:
    """The control: each answer with the vertices of its deepest BFS level
    left unreached, as a traversal stopped one iteration early leaves
    them. ``bfs_rows`` are the hop counts from the same roots."""
    out = np.array(ref_rows, copy=True)
    deepest = bfs_rows.max(axis=1, keepdims=True)
    last = (bfs_rows == deepest) & (deepest > 0)
    out[last] = -1 if alg == "bfs" else np.inf
    return out


def count_wrong(alg: str, got: np.ndarray, want: np.ndarray) -> int:
    """Entries of one answer that differ from the reference."""
    if alg == "bfs":
        return int(np.sum(np.asarray(got, np.int64) != want))
    got = np.asarray(got, np.float64)
    same = (got == want) | (np.isinf(got) & np.isinf(want))
    return int(np.sum(~same))

"""Single-source shortest paths (Graph500 kernel 3): every vertex's
shortest distance from the root, ``inf`` where unreachable, from scipy's
Dijkstra. The edge weights are the served path's integer weights 1..9,
hashed from an edge's endpoints and the run's seed; :func:`edge_weights`
is this benchmark's own copy of that hash. The configurations guarantee
exact answers, so the comparison counts the entries that differ. The
control leaves each answer's deepest hop level unreached."""
from __future__ import annotations

import numpy as np
from scipy.sparse.csgraph import dijkstra

from bench import reference, roofline

PAYLOAD_FIELD = "dist"
# float32 weights beside each 4-byte index
VALUE_BYTES = 4


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


def answers(rows, cols, n, roots, seed: int) -> np.ndarray:
    """[len(roots), n] shortest distances; inf where unreachable."""
    adj = reference.adjacency(rows, cols, n, edge_weights(rows, cols, seed))
    return dijkstra(adj, directed=True, indices=np.asarray(roots))


def count_wrong(got, want) -> int:
    got = np.asarray(got, np.float64)
    same = (got == want) | (np.isinf(got) & np.isinf(want))
    return int(np.sum(~same))


def control(rows, cols, n, roots, seed: int, want) -> np.ndarray:
    hops = reference.hop_counts(rows, cols, n, roots)
    return reference.truncate_last_level(want, hops, np.inf)


def frontier_entries(payloads, rows, cols, n, roots, degrees,
                     trips: int) -> list:
    hops = reference.hop_counts(rows, cols, n, roots)
    return roofline.frontier_entries(hops, degrees, trips)


def least_bytes(n: int, nnz: int, k: int, frontier: list) -> float:
    return roofline.least_bytes(n, nnz, k, frontier, VALUE_BYTES)

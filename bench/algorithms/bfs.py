"""Breadth-first search (Graph500 kernel 2): every vertex's hop count from
the root, ``-1`` where unreachable, from scipy's unweighted shortest
paths. The configurations guarantee exact answers, so the comparison
counts the entries that differ. The control leaves each answer's deepest
level unreached, as a traversal stopped one iteration early would."""
from __future__ import annotations

import numpy as np

from bench import reference, roofline

PAYLOAD_FIELD = "levels"
# a pattern matrix: an entry is its 4-byte index alone
VALUE_BYTES = 0


def answers(rows, cols, n, roots, seed: int) -> np.ndarray:
    return reference.hop_counts(rows, cols, n, roots)


def count_wrong(got, want) -> int:
    return int(np.sum(np.asarray(got, np.int64) != want))


def control(rows, cols, n, roots, seed: int, want) -> np.ndarray:
    return reference.truncate_last_level(want, want, -1)


def frontier_entries(payloads, rows, cols, n, roots, degrees,
                     trips: int) -> list:
    hops = np.stack([np.asarray(p[PAYLOAD_FIELD]) for p in payloads])
    return roofline.frontier_entries(hops, degrees, trips)


def least_bytes(n: int, nnz: int, k: int, frontier: list) -> float:
    return roofline.least_bytes(n, nnz, k, frontier, VALUE_BYTES)

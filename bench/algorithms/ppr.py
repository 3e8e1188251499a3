"""Personalized PageRank from one source: the stationary vector of

    r = (1 - ALPHA) * e_s + ALPHA * sum over entries u -> v of r[u] / deg(u)

(deg(u): u's stored entries), as served with ALPHA = 0.85: each answer
iterates from ``e_s`` until its L1 residual between two iterations is at
most 1e-6, for at most 64 iterations. The reference is a float64 power
iteration over each bucket of 8 roots, run until every root's L1 residual
is under 1e-12 (about 40-45 iterations at Kronecker scale 18).

The comparison counts the answers whose L1 distance from the reference
exceeds ``LIMIT`` = 1e-3, and the limit of that count is 0. The limit
lies between two readings on one TPU v5e at Kronecker scale 18, the
largest distance of 48 sampled answers per run:

* sound runs read at most 9.8e-5 on 10 seeds, and under 1e-4 on 5
  more. Stopping at a residual of 1e-6 errs by at most ALPHA / (1 -
  ALPHA) * 1e-6, about 5.7e-6; the rest is float32 rounding, largest
  where a root of degree 1 hangs on a hub whose 25k entries are summed
  in float32 (scipy in float32 reads 6e-5 there, in float64 3.4e-7);
* the control, this reference with its state rounded to bfloat16 (the
  next precision below) after every iteration under the served stopping
  rule, reads 4.9e-3 on every seed tried.

So the limit sits 10x above the sound runs and 4.9x below the control. A
stop at a residual of 1e-3 (7-9 iterations, errors of 5e-5 to 2.4e-4 in
float64) lies among the sound runs' own rounding, and no comparison of
float32 answers can catch it.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

from bench import reference, roofline

PAYLOAD_FIELD = "rank"
ALPHA = 0.85
LIMIT = 1e-3
DISTANCE = "l1_max"
EXACT_STOP = 1e-12
# the served stopping rule, which the control keeps
SERVED_STOP, SERVED_MAX_ITERS = 1e-6, 64
BUCKET = 8
# float32 values beside each 4-byte index
VALUE_BYTES = 4


def walk_matrix(rows, cols, n):
    """[n, n] with the entry (v, u) = 1 / deg(u) for each stored u -> v."""
    deg = np.bincount(rows, minlength=n).astype(np.float64)
    return reference.adjacency(cols, rows, n, 1.0 / deg[rows])


def power_iteration(walk, roots, stop: float, max_iters: int | None = None,
                    state_dtype=None) -> np.ndarray:
    """[len(roots), n]: each root's float64 iteration from ``e_s``, frozen
    once its L1 residual is at most ``stop`` (or after ``max_iters``).
    ``state_dtype`` rounds the state to that type after every iteration,
    as a state kept in a narrower type would be."""
    n, k = walk.shape[0], len(roots)
    e = np.zeros((n, k))
    e[np.asarray(roots), np.arange(k)] = 1.0
    r = e.copy()
    active = np.ones(k, bool)
    it = 0
    while active.any() and (max_iters is None or it < max_iters):
        r_new = (1 - ALPHA) * e[:, active] + ALPHA * (walk @ r[:, active])
        if state_dtype is not None:
            r_new = r_new.astype(state_dtype).astype(np.float64)
        res = np.abs(r_new - r[:, active]).sum(axis=0)
        r[:, active] = r_new
        active[np.flatnonzero(active)[res <= stop]] = False
        it += 1
    return r.T


def _by_bucket(rows, cols, n, roots, **kw) -> np.ndarray:
    """:func:`power_iteration` over buckets of ``BUCKET`` roots, the
    buckets on threads (scipy's sparse products release the GIL)."""
    walk = walk_matrix(rows, cols, n)
    blocks = [roots[lo:lo + BUCKET] for lo in range(0, len(roots), BUCKET)]
    workers = max(1, min(len(blocks), os.cpu_count() or 1, 8))
    with ThreadPoolExecutor(workers) as pool:
        parts = list(pool.map(lambda b: power_iteration(walk, b, **kw),
                              blocks))
    return np.concatenate(parts)


def answers(rows, cols, n, roots, seed: int) -> np.ndarray:
    return _by_bucket(rows, cols, n, roots, stop=EXACT_STOP)


def distance(got, want) -> float:
    return float(np.abs(np.asarray(got, np.float64) - want).sum())


def count_wrong(got, want) -> int:
    return int(distance(got, want) > LIMIT)


def control(rows, cols, n, roots, seed: int, want) -> np.ndarray:
    return _by_bucket(rows, cols, n, roots, stop=SERVED_STOP,
                      max_iters=SERVED_MAX_ITERS,
                      state_dtype=ml_dtypes.bfloat16)


def frontier_entries(payloads, rows, cols, n, roots, degrees,
                     trips: int) -> list:
    """Iteration ``t`` reads from every vertex that holds mass in any query
    of the bucket: those within ``t`` hops of its root."""
    hops = reference.hop_counts(rows, cols, n, roots)
    reached = hops >= 0
    return [int(degrees[np.any(reached & (hops <= t), axis=0)].sum())
            for t in range(trips)]


def least_bytes(n: int, nnz: int, k: int, frontier: list) -> float:
    return roofline.least_bytes(n, nnz, k, frontier, VALUE_BYTES)

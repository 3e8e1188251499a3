"""The one generator of query streams; a mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) gives:

* ``rate_per_s``: the offered load, an open loop: queries are sent at their
  due times whether or not earlier ones have been answered.
* ``arrivals``: ``"even"``, one query every ``1 / rate_per_s`` seconds (a
  paced client, as YCSB's ``-target`` throttle spaces operations), or
  ``"poisson"``, exponential gaps at ``rate_per_s``.
* ``batch``: the server's bucket size (``batch_size`` of the tenant).
* ``max_wait_s``: the scheduler's window budget.
* ``mix``: algorithm -> share of the queries, interleaved in a fixed
  pattern (for 50/50, every other query).
* ``roots``: ``"uniform"`` over the vertices of degree >= 1 (Graph500's
  search-key rule), or ``"zipf"`` with ``zipf_theta`` over a seeded ranking
  of those vertices.

Every seed offers the same work at the same times: one fixed set of due
times (Poisson gaps are the exponential distribution's quantiles of evenly
spaced levels, scaled to fill the window, in a seed-drawn order) and, per
algorithm, one fixed multiset of roots in a seed-drawn order (see
:func:`make`).

Warm-up roots (one bucket per algorithm) are drawn first and removed from
the candidates, so no measured query is answered from warm-up results.
"""
from __future__ import annotations

import numpy as np


def _zipf_probabilities(count: int, theta: float) -> np.ndarray:
    p = 1.0 / np.arange(1, count + 1, dtype=np.float64) ** theta
    return p / p.sum()


def arrivals(kind: str, rate: float, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due offsets in [0, seconds), the first at 0, ``rate`` a second."""
    count = max(1, int(round(rate * seconds)))
    if kind == "even":
        return np.arange(count) * (seconds / count)
    if kind != "poisson":
        raise ValueError(f"unknown arrivals {kind!r}")
    levels = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-levels)
    gaps *= seconds / gaps.sum()
    rng.shuffle(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def interleave(shares: np.ndarray, length: int) -> np.ndarray:
    """Algorithm index per position: each next query goes to the algorithm
    furthest behind its share."""
    shares = shares / shares.sum()
    given = np.zeros(shares.size)
    out = np.empty(length, np.int64)
    for i in range(length):
        a = int(np.argmax(shares * (i + 1) - given))
        out[i] = a
        given[a] += 1
    return out


def make(traffic: dict, degrees: np.ndarray, perm: np.ndarray, seed: int,
         seconds: float):
    """Returns (warm-up roots per algorithm, the measured stream): every
    query due in a window of ``seconds`` as (due offset, algorithm, root).

    Per algorithm the roots are one fixed multiset of structural vertices,
    the same for every seed; ``seed`` draws their order (and the order of
    Poisson gaps), and ``perm`` (structural vertex v is served as
    ``perm[v]``, see bench/graphgen.py) their labels. So every seed offers
    the same work on an isomorphic graph, in another order."""
    fixed = np.random.default_rng(0x7AF1C)
    by_seed = np.random.default_rng([seed, 0x7AF1C])
    candidates = fixed.permutation(np.flatnonzero(degrees[perm] > 0))
    algs = sorted(traffic["mix"])
    batch = int(traffic["batch"])
    warm = {}
    for i, alg in enumerate(algs):
        warm[alg] = [int(perm[v])
                     for v in candidates[i * batch:(i + 1) * batch]]
    candidates = candidates[len(algs) * batch:]
    due = arrivals(traffic["arrivals"], float(traffic["rate_per_s"]),
                   seconds, by_seed)
    alg_idx = interleave(
        np.asarray([float(traffic["mix"][a]) for a in algs]), due.size)
    kind = traffic["roots"]
    if kind == "zipf":
        p = _zipf_probabilities(candidates.size, float(traffic["zipf_theta"]))
    elif kind != "uniform":
        raise ValueError(f"unknown root distribution {kind!r}")
    roots = np.empty(due.size, np.int64)
    for a in range(len(algs)):
        slots = np.flatnonzero(alg_idx == a)
        if kind == "uniform":
            picks = fixed.integers(0, candidates.size, slots.size)
        else:
            picks = fixed.choice(candidates.size, size=slots.size, p=p)
        roots[slots] = perm[candidates[picks[by_seed.permutation(slots.size)]]]
    return warm, [(float(t), algs[a], int(r))
                  for t, a, r in zip(due, alg_idx, roots)]

"""DIMACS10's random geometric graphs (``rgg_n_2_X_s0``): ``2**scale``
points drawn uniformly in the unit square, and an edge between every two
points closer than ``radius_factor * sqrt(ln n / n)`` (0.55 in DIMACS10).
The points come from ``rng``, not from DIMACS10's own generator, so the
family is reproduced by its n and radius, not point for point."""
from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree


def radius(n: int, radius_factor: float) -> float:
    return float(radius_factor) * float(np.sqrt(np.log(n) / n))


def tuples(cfg: dict, rng: np.random.Generator):
    n = 1 << int(cfg["scale"])
    points = rng.random((n, 2))
    pairs = cKDTree(points).query_pairs(radius(n, cfg["radius_factor"]),
                                        output_type="ndarray")
    return pairs[:, 0].astype(np.int64), pairs[:, 1].astype(np.int64), n

"""The GAP Benchmark Suite's ``-u`` graphs: ``edgefactor * 2**S`` tuples
with both endpoints uniform over the vertices."""
from __future__ import annotations

import numpy as np


def tuples(cfg: dict, rng: np.random.Generator):
    scale = int(cfg["scale"])
    n, m = 1 << scale, int(cfg["edgefactor"]) << scale
    return rng.integers(0, n, m), rng.integers(0, n, m), n

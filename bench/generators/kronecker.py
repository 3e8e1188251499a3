"""Graph500's Kronecker graphs: the reference generator's
(``kronecker_generator.m``, specification section 3) ``edgefactor * 2**S``
edge tuples, each of its ``S`` bit levels drawn with the initiator
probabilities A, B, C (D = 1 - A - B - C). Graph500's permutations are left
to bench/graphgen.py."""
from __future__ import annotations

import numpy as np


def bit_levels(scale: int, edgefactor: int, a: float, b: float, c: float,
               rng: np.random.Generator):
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


def tuples(cfg: dict, rng: np.random.Generator):
    scale = int(cfg["scale"])
    a, b, c = (float(cfg["initiator"][k]) for k in ("A", "B", "C"))
    src, dst = bit_levels(scale, int(cfg["edgefactor"]), a, b, c, rng)
    return src, dst, 1 << scale

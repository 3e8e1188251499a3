"""Single-Source Shortest Path over the ⟨min,+⟩ semiring (Table 1).

Bellman-Ford with frontier pruning: each iteration relaxes only from
vertices whose distance changed last round (the sparse frontier), i.e.
cand = Aᵀ ⊕.⊗ changed, dist' = min(dist, cand). The changed-set density
drives the adaptive SpMSpV↔SpMV switch exactly as in BFS. The loop runs
until no distance improves (within ``n_true`` rounds for positive
weights) and counts its trips as BFS does.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import MIN_PLUS
from repro.graphs.engine import GraphEngine, nonzeros_of

Array = jax.Array


class SSSPResult(NamedTuple):
    dist: Array         # f32 [n]; +inf = unreachable
    iterations: Array   # scalar int32: loop trips
    spmv_trips: Array   # scalar int32: of those, trips on the SpMV
    consumed: Array     # scalar int32: changed-set nonzeros read


def sssp(engine: GraphEngine, source: int,
         policy: str = "adaptive") -> SSSPResult:
    sr = engine.sr
    assert sr.name == MIN_PLUS.name
    n = engine.n
    step = engine.step_fn(policy)

    def cond(state):
        dist, changed, it, done, spmv, consumed = state
        return (~done) & (it < engine.n_true)

    def body(state):
        dist, changed, it, done, spmv, consumed = state
        nz = nonzeros_of(changed, sr, engine.n_true)
        density = nz.astype(jnp.float32) / float(engine.n_true)
        used = jnp.where(policy == "spmv", 1,
                         jnp.where(policy == "spmspv", 0,
                                   (density > engine.threshold).astype(jnp.int32)))
        cand = step(changed, density)          # cand[v] = min_u changed[u] + w(u,v)
        new_dist = jnp.minimum(dist, cand)
        new_changed = jnp.where(new_dist < dist, new_dist, jnp.inf)
        done = jnp.sum(new_changed != jnp.inf) == 0
        return (new_dist, new_changed, it + 1, done, spmv + used,
                consumed + nz)

    dist0 = jnp.full((n,), jnp.inf, jnp.float32).at[source].set(0.0)
    changed0 = jnp.full((n,), jnp.inf, jnp.float32).at[source].set(0.0)
    zero = jnp.asarray(0, jnp.int32)

    dist, changed, it, done, spmv, consumed = jax.lax.while_loop(
        cond, body, (dist0, changed0, zero, jnp.asarray(False), zero, zero))
    return SSSPResult(dist[: engine.n_true], it, spmv, consumed)


def sssp_reference(rows: np.ndarray, cols: np.ndarray, weights: np.ndarray,
                   n: int, source: int) -> np.ndarray:
    """CPU oracle: scipy Dijkstra on the directed weighted edge list."""
    import scipy.sparse as sp
    import scipy.sparse.csgraph as csgraph

    a = sp.csr_matrix((weights, (rows, cols)), shape=(n, n))
    return csgraph.dijkstra(a, indices=source, directed=True)

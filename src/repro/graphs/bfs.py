"""Breadth-First Search over the ⟨∨,∧⟩ semiring (paper §5.1, Table 1).

Level-synchronous pull BFS: fₖ₊₁ = (Aᵀ ⊕.⊗ fₖ) ∧ ¬visited. The frontier
density is monitored every level; the adaptive policy switches SpMSpV→SpMV
once it crosses the decision-tree threshold (§4.2) — all inside one jitted
`lax.while_loop` (`lax.cond` makes the switch free, unlike UPMEM's
host-side check). The loop runs until the frontier is empty, at any depth,
and counts its trips, those on the SpMV and the frontier nonzeros read, as
the batched runners (graphs/multi.py) count each row's.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import BOOL_OR_AND
from repro.graphs.engine import GraphEngine, nonzeros_of

Array = jax.Array


class BFSResult(NamedTuple):
    levels: Array       # int32 [n]; -1 = unreached
    iterations: Array   # scalar int32: loop trips
    spmv_trips: Array   # scalar int32: of those, trips on the SpMV
    consumed: Array     # scalar int32: frontier nonzeros read over all trips


def bfs(engine: GraphEngine, source: int,
        policy: str = "adaptive") -> BFSResult:
    sr = engine.sr
    assert sr.name == BOOL_OR_AND.name
    n = engine.n
    step = engine.step_fn(policy)

    def cond(state):
        frontier, visited, levels, it, done, spmv, consumed = state
        return (~done) & (it < engine.n_true)

    def body(state):
        frontier, visited, levels, it, done, spmv, consumed = state
        nz = nonzeros_of(frontier, sr, engine.n_true)
        density = nz.astype(jnp.float32) / float(engine.n_true)
        used = jnp.where(policy == "spmv", 1,
                         jnp.where(policy == "spmspv", 0,
                                   (density > engine.threshold).astype(jnp.int32)))
        y = step(frontier, density)
        new_frontier = jnp.where((y != sr.zero) & (visited == 0),
                                 jnp.asarray(1, sr.dtype), jnp.asarray(0, sr.dtype))
        levels = jnp.where((new_frontier != 0) & (levels < 0), it + 1, levels)
        visited = jnp.where(new_frontier != 0, 1, visited)
        done = jnp.sum(new_frontier) == 0
        return (new_frontier, visited, levels, it + 1, done, spmv + used,
                consumed + nz)

    frontier0 = jnp.zeros((n,), sr.dtype).at[source].set(1)
    visited0 = jnp.zeros((n,), jnp.int32).at[source].set(1)
    levels0 = jnp.full((n,), -1, jnp.int32).at[source].set(0)
    zero = jnp.asarray(0, jnp.int32)

    frontier, visited, levels, it, done, spmv, consumed = jax.lax.while_loop(
        cond, body, (frontier0, visited0, levels0, zero,
                     jnp.asarray(False), zero, zero))
    return BFSResult(levels[: engine.n_true], it, spmv, consumed)


def bfs_reference(rows: np.ndarray, cols: np.ndarray, n: int, source: int) -> np.ndarray:
    """CPU oracle: classic queue BFS over the directed edge list."""
    adj_ptr = np.zeros(n + 1, np.int64)
    np.add.at(adj_ptr, rows + 1, 1)
    adj_ptr = np.cumsum(adj_ptr)
    order = np.argsort(rows, kind="stable")
    adj = cols[order]
    levels = np.full(n, -1, np.int32)
    levels[source] = 0
    q = [source]
    while q:
        nq = []
        for u in q:
            for v in adj[adj_ptr[u]: adj_ptr[u + 1]]:
                if levels[v] < 0:
                    levels[v] = levels[u] + 1
                    nq.append(int(v))
        q = nq
    return levels

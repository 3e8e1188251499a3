"""Whole-graph analytics on the semiring engine (paper §5.1's application
families beyond frontier traversal; PrIM's whole-matrix workload regime).

Where BFS/SSSP/PPR push a sparse frontier, these four apps iterate over the
*entire* vertex set (dense vectors, SpMV every step) or multiply the
adjacency by itself (masked SpGEMM) — the partitioning/communication regime
the paper's Fig. 3 strategies were designed around:

* ``connected_components`` — min-label flooding over ⟨min,×⟩ (Table-1
  extension): l ← l ⊕ (Aᵀ ⊕.⊗ l) until fixpoint; labels are component
  minima, integer-valued, so engine output matches the numpy reference
  element-exactly.
* ``pagerank``            — full power iteration over ⟨+,×⟩ to
  ε-convergence, uniform teleport (re-exported from graphs/ppr.py; the
  all-vertices, dense-from-step-0 counterpart of PPR).
* ``triangle_count``      — C = (L ⊕.⊗ Lᵀ) ⊙ L over ⟨+,∧⟩ with L the
  strict lower triangle; Σ C counts each triangle exactly once. The mask
  rides the core.spgemm masked-SpGEMM kernel (element or Pallas tile path).
* ``kcore``               — iterative degree peel via masked SpMV over
  ⟨+,×⟩: alive-degrees come from one SpMV of the alive indicator, the
  alive mask filters the result, vertices below k drop until fixpoint;
  survivors at k have coreness ≥ k.

Every app has a sequential numpy reference; integer-valued outputs (CC
labels, triangle totals, coreness) must match element-exactly
(tests/test_analytics.py, across the road/uniform/rmat Table-2 families).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats
from repro.core.semiring import MIN_TIMES, PLUS_AND, PLUS_TIMES
from repro.core.spgemm import spgemm_masked
from repro.graphs.datasets import Graph
from repro.graphs.engine import GraphEngine
from repro.graphs.ppr import PPRResult, pagerank, pagerank_reference  # noqa: F401

Array = jax.Array


# ---------------------------------------------------------------------------
# Connected components
# ---------------------------------------------------------------------------

class CCResult(NamedTuple):
    labels: Array        # int32 [n]; label = smallest vertex id in component
    n_components: Array  # scalar int32
    iterations: Array    # scalar int32


def connected_components(engine: GraphEngine, max_iters: int | None = None,
                         labels0=None) -> CCResult:
    """Min-label propagation: every vertex starts labelled with its own id
    (1-based: ⟨min,×⟩ operands must stay strictly positive) and repeatedly
    ⊕-absorbs its neighbours' labels. Converges in O(diameter) rounds to
    the component minimum. Labels stay dense, so the SpMV kernel runs every
    round — no adaptive switch, the opposite regime from BFS.

    ``labels0`` seeds the flood with 0-based labels ([n_true] ints) instead
    of each vertex's own id — the incremental label-repair path of
    graphs/dynamic.py. The seed must be pointwise ≥ the true component
    minima with every merged region reset to own ids (min-flooding only
    lowers labels); then the fixpoint is the exact cold-start answer in
    however many rounds the repaired region's diameter needs."""
    sr = engine.sr
    assert sr.name == MIN_TIMES.name, sr.name
    n, n_true = engine.n, engine.n_true
    # labels live in the semiring's float32 domain: beyond 2^24 distinct
    # ids they would silently collide — fail loudly instead
    assert n_true <= 2 ** 24, f"float32 labels cap CC at 2^24 vertices, got {n_true}"
    max_iters = max_iters or n_true

    if labels0 is None:
        l0 = jnp.arange(1, n_true + 1, dtype=sr.dtype)
    else:
        seed = np.asarray(labels0)
        assert seed.shape == (n_true,), seed.shape
        l0 = jnp.asarray(seed + 1, sr.dtype)
    l0 = jnp.pad(l0, (0, n - n_true), constant_values=sr.zero)

    def cond(state):
        _l, it, done = state
        return (~done) & (it < max_iters)

    def body(state):
        l, it, _done = state
        y = engine.spmv_fn(l)
        new = jnp.minimum(l, y)
        return new, it + 1, jnp.all(new == l)

    l, it, _ = jax.lax.while_loop(
        cond, body, (l0, jnp.asarray(0, jnp.int32), jnp.asarray(False)))
    labels = l[:n_true].astype(jnp.int32) - 1
    n_components = jnp.sum(labels == jnp.arange(n_true, dtype=jnp.int32))
    return CCResult(labels, n_components.astype(jnp.int32), it)


def cc_reference(rows: np.ndarray, cols: np.ndarray, n: int) -> np.ndarray:
    """Sequential union-find; returns per-vertex min-id component labels."""
    parent = np.arange(n, dtype=np.int64)

    def find(v: int) -> int:
        root = v
        while parent[root] != root:
            root = parent[root]
        while parent[v] != root:           # path compression
            parent[v], v = root, parent[v]
        return root

    for u, v in zip(rows.tolist(), cols.tolist()):
        ru, rv = find(u), find(v)
        if ru != rv:
            parent[max(ru, rv)] = min(ru, rv)  # min-id root ⇒ min-id label
    return np.array([find(v) for v in range(n)], dtype=np.int32)


# ---------------------------------------------------------------------------
# Triangle counting
# ---------------------------------------------------------------------------

class TriangleResult(NamedTuple):
    total: Array     # scalar int32 triangle count (x64 is disabled)
    per_edge: Array  # int32 [n, n] masked wedge counts (C = L·Lᵀ ⊙ L)


def lower_triangle(g: Graph):
    """Strict lower triangle of the (symmetric) adjacency as an edge list."""
    sel = g.rows > g.cols
    return g.rows[sel].astype(np.int32), g.cols[sel].astype(np.int32)


def triangle_problem(g: Graph, impl: str = "csr",
                     block: tuple[int, int] = (128, 128)):
    """Host-side build (the paper's untimed matrix-load phase): returns
    ``(a, b, mask, impl_kw)`` ready for spgemm_masked — L in the container
    ``impl`` selects, Lᵀ dense, and L itself as the structural mask."""
    sr = PLUS_AND
    n = g.n
    lr, lc = lower_triangle(g)
    ones = np.ones(lr.shape[0], np.int32)
    b = np.zeros((n, n), np.int32)      # Lᵀ dense
    b[lc, lr] = 1
    mask = np.zeros((n, n), np.int32)   # L dense (structural mask)
    mask[lr, lc] = 1

    if impl == "csr":
        return (formats.build_csr(lr, lc, ones, (n, n), sr),
                jnp.asarray(b), jnp.asarray(mask), "auto")
    if impl in ("bsr", "bsr_ref"):
        a = formats.build_bsr_padded(lr, lc, ones, (n, n), sr, block=block)
        bp = np.zeros((a.shape[1], n), np.int32)
        bp[:n] = b
        mp = np.zeros((a.shape[0], n), np.int32)
        mp[:n] = mask
        return (a, jnp.asarray(bp), jnp.asarray(mp),
                "ref" if impl == "bsr_ref" else "auto")
    if impl == "dense":
        return jnp.asarray(mask), jnp.asarray(b), jnp.asarray(mask), "auto"
    raise ValueError(impl)


def triangle_count(g: Graph, impl: str = "csr",
                   block: tuple[int, int] = (128, 128)) -> TriangleResult:
    """Masked SpGEMM triangle count: C[i,j] = |{k : k<j<i, (i,k),(j,k)∈E}|
    for every edge (i,j) of L, so ΣC counts each triangle (k<j<i) once.
    ``impl`` picks L's container: "csr" (element path), "bsr"/"bsr_ref"
    (Pallas tile kernel / its jnp oracle), "dense" (blocked reference)."""
    sr = PLUS_AND
    a, b, mask, impl_kw = triangle_problem(g, impl, block)
    c = spgemm_masked(a, b, sr, mask, impl=impl_kw)[: g.n]
    total = jnp.sum(c)
    return TriangleResult(total, c)


def triangle_reference(rows: np.ndarray, cols: np.ndarray, n: int) -> int:
    """Sequential counter: per L-edge (i,j), intersect the lower-neighbour
    sets of i and j (the classic merge-based algorithm, int64-exact)."""
    lower: list[set] = [set() for _ in range(n)]
    for u, v in zip(rows.tolist(), cols.tolist()):
        if u > v:
            lower[u].add(v)
    total = 0
    for u in range(n):
        for v in lower[u]:
            total += len(lower[u] & lower[v])
    return total


# ---------------------------------------------------------------------------
# k-core decomposition
# ---------------------------------------------------------------------------

class KCoreResult(NamedTuple):
    coreness: Array    # int32 [n]; max k s.t. vertex survives the k-peel
    max_core: Array    # scalar int32
    iterations: Array  # total SpMV peel rounds across all k


def kcore(engine: GraphEngine, max_k: int | None = None) -> KCoreResult:
    """Degree peel via masked SpMV over ⟨+,×⟩ with unit weights: one SpMV
    of the alive indicator gives every vertex its alive-degree; the alive
    mask filters the result (GraphBLAS masked matvec); vertices under k
    drop and the peel repeats until stable. Survivors get coreness k; k
    then increments until no vertex survives."""
    sr = engine.sr
    assert sr.name == PLUS_TIMES.name, sr.name
    n, n_true = engine.n, engine.n_true
    max_k = max_k or n_true

    alive0 = jnp.pad(jnp.ones((n_true,), sr.dtype), (0, n - n_true),
                     constant_values=sr.zero)
    core0 = jnp.zeros((n_true,), jnp.int32)

    def peel_cond(state):
        _alive, changed, _k, _it = state
        return changed

    def peel_body(state):
        alive, _changed, k, it = state
        deg = engine.spmv_fn(alive)
        # `keep` both applies the alive mask and peels under-k vertices
        keep = (alive != 0) & (deg >= k)
        new_alive = jnp.where(keep, alive, jnp.asarray(sr.zero, sr.dtype))
        changed = jnp.any(new_alive != alive)
        return new_alive, changed, k, it + 1

    def outer_cond(state):
        alive, _core, k, _it = state
        return jnp.any(alive != 0) & (k <= max_k)

    def outer_body(state):
        alive, core, k, it = state
        alive, _, _, it = jax.lax.while_loop(
            peel_cond, peel_body,
            (alive, jnp.asarray(True), k.astype(sr.dtype), it))
        core = jnp.where(alive[:n_true] != 0, k, core)
        return alive, core, k + 1, it

    _, core, _, it = jax.lax.while_loop(
        outer_cond, outer_body,
        (alive0, core0, jnp.asarray(1, jnp.int32), jnp.asarray(0, jnp.int32)))
    return KCoreResult(core, jnp.max(core), it)


def kcore_reference(rows: np.ndarray, cols: np.ndarray, n: int) -> np.ndarray:
    """Sequential peel with the same round structure (recompute alive
    degrees, drop everything under k, repeat; then k += 1)."""
    coreness = np.zeros(n, np.int32)
    alive = np.ones(n, bool)
    k = 1
    while alive.any():
        while True:
            sel = alive[rows] & alive[cols]
            deg = np.bincount(rows[sel], minlength=n)
            drop = alive & (deg < k)
            if not drop.any():
                break
            alive &= ~drop
        coreness[alive] = k
        k += 1
    return coreness

"""Personalized PageRank over ⟨+,×⟩ (Table 1).

Power iteration on the column-stochastic matrix P = Aᵀ D⁻¹:
    r ← (1−α)·e_s + α·(P ⊕.⊗ r)
The personalization vector e_s is a single vertex, so r starts maximally
sparse and densifies over iterations — the paper's motivating case for
adaptive SpMSpV→SpMV switching in PPR.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import PLUS_TIMES
from repro.graphs.engine import GraphEngine, nonzeros_of

Array = jax.Array


class PPRResult(NamedTuple):
    rank: Array
    iterations: Array   # scalar int32: loop trips
    spmv_trips: Array   # scalar int32: of those, trips on the SpMV
    consumed: Array     # scalar int32: rank-vector nonzeros read
    residual: Array


def ppr(engine: GraphEngine, source: int, alpha: float = 0.85,
        max_iters: int = 50, tol: float = 1e-6,
        policy: str = "adaptive") -> PPRResult:
    sr = engine.sr
    assert sr.name == PLUS_TIMES.name
    n = engine.n
    step = engine.step_fn(policy)
    e_s = jnp.zeros((n,), jnp.float32).at[source].set(1.0)

    def cond(state):
        r, it, res, spmv, consumed = state
        return (res > tol) & (it < max_iters)

    def body(state):
        r, it, res, spmv, consumed = state
        nz = nonzeros_of(r, sr, engine.n_true)
        density = nz.astype(jnp.float32) / float(engine.n_true)
        used = jnp.where(policy == "spmv", 1,
                         jnp.where(policy == "spmspv", 0,
                                   (density > engine.threshold).astype(jnp.int32)))
        pr = step(r, density)
        r_new = (1.0 - alpha) * e_s + alpha * pr
        res = jnp.sum(jnp.abs(r_new - r))
        return (r_new, it + 1, res, spmv + used, consumed + nz)

    zero = jnp.asarray(0, jnp.int32)
    r, it, res, spmv, consumed = jax.lax.while_loop(
        cond, body, (e_s, zero, jnp.asarray(jnp.inf), zero, zero))
    return PPRResult(r[: engine.n_true], it, spmv, consumed, res)


def pagerank(engine: GraphEngine, alpha: float = 0.85, max_iters: int = 50,
             tol: float = 1e-6, policy: str = "spmv",
             r0=None) -> PPRResult:
    """Global PageRank [65] — the paper's §5.1 family, uniform teleport.
    r starts dense (1/n everywhere), so SpMV is the natural kernel for the
    whole run — the opposite end of the density spectrum from PPR.

    ``r0`` warm-starts the power iteration from a previous rank vector
    ([n_true]; e.g. the pre-delta ranks in graphs/dynamic.py): the
    fixpoint is the same, but a start near it converges in fewer
    iterations — the iteration-count win benchmarks/dynamic_updates.py
    tracks."""
    sr = engine.sr
    assert sr.name == PLUS_TIMES.name
    n = engine.n
    step = engine.step_fn(policy)
    e = jnp.full((n,), 1.0 / engine.n_true, jnp.float32)
    e = e.at[engine.n_true:].set(0.0)
    if r0 is None:
        start = e
    else:
        r0 = jnp.asarray(np.asarray(r0, np.float32))
        assert r0.shape == (engine.n_true,), r0.shape
        start = jnp.pad(r0, (0, n - engine.n_true))

    def cond(state):
        r, it, res, spmv, consumed = state
        return (res > tol) & (it < max_iters)

    def body(state):
        r, it, res, spmv, consumed = state
        nz = nonzeros_of(r, sr, engine.n_true)
        density = nz.astype(jnp.float32) / float(engine.n_true)
        used = jnp.where(policy == "spmv", 1,
                         jnp.where(policy == "spmspv", 0,
                                   (density > engine.threshold).astype(jnp.int32)))
        pr = step(r, density)
        r_new = (1.0 - alpha) * e + alpha * pr
        res = jnp.sum(jnp.abs(r_new - r))
        return (r_new, it + 1, res, spmv + used, consumed + nz)

    zero = jnp.asarray(0, jnp.int32)
    r, it, res, spmv, consumed = jax.lax.while_loop(
        cond, body, (start, zero, jnp.asarray(jnp.inf), zero, zero))
    return PPRResult(r[: engine.n_true], it, spmv, consumed, res)


def pagerank_reference(rows: np.ndarray, cols: np.ndarray, n: int,
                       alpha: float = 0.85, iters: int = 50) -> np.ndarray:
    deg = np.maximum(np.bincount(rows, minlength=n), 1).astype(np.float64)
    p = np.zeros((n, n))
    p[cols, rows] = 1.0 / deg[rows]
    e = np.full(n, 1.0 / n)
    r = e.copy()
    for _ in range(iters):
        r_new = (1 - alpha) * e + alpha * (p @ r)
        if np.abs(r_new - r).sum() <= 1e-6:
            return r_new
        r = r_new
    return r


def ppr_reference(rows: np.ndarray, cols: np.ndarray, n: int, source: int,
                  alpha: float = 0.85, iters: int = 50) -> np.ndarray:
    """numpy oracle: same power iteration with dense matrices."""
    deg = np.maximum(np.bincount(rows, minlength=n), 1).astype(np.float64)
    p = np.zeros((n, n))
    p[cols, rows] = 1.0 / deg[rows]
    e = np.zeros(n)
    e[source] = 1.0
    r = e.copy()
    for _ in range(iters):
        r_new = (1 - alpha) * e + alpha * (p @ r)
        if np.abs(r_new - r).sum() <= 1e-6:
            r = r_new
            break
        r = r_new
    return r

"""Traversal engine: builds matvec closures over a graph and runs the
adaptive SpMSpV↔SpMV iteration skeleton shared by BFS/SSSP/PPR (§4.2).

Apps are written against two closures (spmv_fn, spmspv_fn), both taking and
returning *dense* vectors — the SpMSpV branch compresses internally. This
keeps `lax.cond` signatures uniform and lets the same app code run on a
single device (element or Pallas kernels) or on a mesh (distributed
closures built from core.distributed).

The engine keeps its matrices as one pytree (``mats``) next to the
closures built over them. ``bind(mats)`` rebuilds the closures over other
arrays of the same structure — traced jit arguments, in the served
runners of graphs/multi.py — so a compiled program takes the graph as
parameters instead of embedding it as constants.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import formats
from repro.core.adaptive import DecisionStump, adaptive_matvec_batch
from repro.core.semiring import Semiring
from repro.core.spmspv import frontier_from_dense, spmspv, spmspv_batch_union
from repro.core.spmv import spmv, spmv_batch
from repro.graphs.datasets import Graph

Array = jax.Array
MatvecFn = Callable[[Array], Array]


@dataclasses.dataclass
class GraphEngine:
    """Per-(graph, semiring) compiled state: the transposed adjacency in the
    formats the two kernels want, plus the adaptive switch threshold.

    ``spmv_batch_fn``/``spmspv_batch_fn`` are the [B, n]-block counterparts
    of the single-vector closures (vmapped over the same adjacency), the
    substrate of the multi-source traversals in graphs/multi.py."""

    spmv_fn: MatvecFn
    spmspv_fn: MatvecFn
    n: int                 # padded vector length
    n_true: int
    threshold: float
    graph_class: str
    sr: Semiring
    spmv_batch_fn: MatvecFn | None = None
    spmspv_batch_fn: MatvecFn | None = None
    mats: tuple = ()       # (SpMV matrix, SpMSpV matrix) pytree
    make_fns: Callable[..., tuple] | None = None   # mats -> the 4 closures

    def bind(self, mats) -> "GraphEngine":
        """This engine with its closures rebuilt over ``mats`` (same tree
        structure as ``self.mats``, e.g. traced jit arguments)."""
        spmv_fn, spmspv_fn, spmv_batch_fn, spmspv_batch_fn = \
            self.make_fns(*mats)
        return dataclasses.replace(
            self, mats=mats, spmv_fn=spmv_fn, spmspv_fn=spmspv_fn,
            spmv_batch_fn=spmv_batch_fn, spmspv_batch_fn=spmspv_batch_fn)

    def adaptive_fn(self, x: Array, density: Array) -> Array:
        """One adaptive matvec: SpMV above the density threshold else SpMSpV."""
        return jax.lax.cond(density > self.threshold, self.spmv_fn, self.spmspv_fn, x)

    def step_fn(self, policy: str) -> Callable[[Array, Array], Array]:
        if policy == "spmv":
            return lambda x, _d: self.spmv_fn(x)
        if policy == "spmspv":
            return lambda x, _d: self.spmspv_fn(x)
        if policy == "adaptive":
            return self.adaptive_fn
        raise ValueError(policy)

    def adaptive_batch_fn(self, xs: Array, densities: Array) -> Array:
        """Per-query adaptive matvec over a [B, n] block (see
        core.adaptive.adaptive_matvec_batch for the select semantics)."""
        return adaptive_matvec_batch(self.spmspv_batch_fn, self.spmv_batch_fn,
                                     xs, densities, self.threshold,
                                     zero=self.sr.zero)

    def batch_step_fn(self, policy: str) -> Callable[[Array, Array], Array]:
        """[B, n]-block counterpart of step_fn: fn(xs, densities) -> ys."""
        if self.spmv_batch_fn is None or self.spmspv_batch_fn is None:
            raise ValueError("engine was built without batched closures")
        if policy == "spmv":
            return lambda xs, _d: self.spmv_batch_fn(xs)
        if policy == "spmspv":
            return lambda xs, _d: self.spmspv_batch_fn(xs)
        if policy == "adaptive":
            return self.adaptive_batch_fn
        raise ValueError(policy)


def content_keyed_weights(rows: np.ndarray, cols: np.ndarray,
                          seed: int = 0) -> np.ndarray:
    """Deterministic per-edge weights in {1..9} keyed on the edge's
    *endpoints* (splitmix-style integer hash), not its position in the
    edge list. Positional weights (the legacy rng draw) reshuffle on any
    edge insert/delete, which would invalidate every cached SSSP answer
    and every warm-start state on every delta; content-keyed weights keep
    untouched edges' weights stable across snapshots — the property the
    streaming-update stack (graphs/dynamic.py, serve mutate) requires."""
    seed_mix = np.uint64((seed * 0xD6E8FEB86659FD93) % (1 << 64))
    h = (np.asarray(rows, np.uint64) * np.uint64(0x9E3779B97F4A7C15)
         ^ np.asarray(cols, np.uint64) * np.uint64(0xC2B2AE3D27D4EB4F)
         ^ seed_mix)
    h ^= h >> np.uint64(33)
    h *= np.uint64(0xFF51AFD7ED558CCD)
    h ^= h >> np.uint64(29)
    return (1 + (h % np.uint64(9))).astype(np.float32)


def edge_values(g: Graph, sr: Semiring, weighted: bool, seed: int = 0,
                normalize: bool = False,
                content_keyed: bool = False) -> np.ndarray:
    if sr.name == "bool_or_and":
        return np.ones(g.nnz, np.int32)
    if weighted:
        if content_keyed:
            vals = content_keyed_weights(g.rows, g.cols, seed)
        else:
            rng = np.random.default_rng(seed)
            vals = rng.integers(1, 10, g.nnz).astype(np.float32)
    else:
        vals = np.ones(g.nnz, np.float32)
    if normalize:  # column-stochastic for PPR: weight(u→v) = 1/outdeg(u)
        deg = np.maximum(g.out_degrees(), 1)
        vals = vals / deg[g.rows]
    return vals


def build_engine(g: Graph, sr: Semiring, stump: DecisionStump | None = None,
                 fmt_spmv: str = "csr", fmt_spmspv: str = "csc",
                 weighted: bool = False, normalize: bool = False,
                 seed: int = 0, f_max: int | None = None,
                 content_keyed: bool = False) -> GraphEngine:
    """Build single-device closures over the *transposed* adjacency
    (traversals compute y = Aᵀ ⊕.⊗ x: pull from in-neighbours).
    ``content_keyed`` swaps the positional weight draw for endpoint-hash
    weights (see :func:`content_keyed_weights`) so engines built on
    successive delta snapshots agree on every surviving edge."""
    stump = stump or DecisionStump()
    vals = edge_values(g, sr, weighted, seed, normalize, content_keyed)
    # transpose: swap row/col
    rows, cols = g.cols.astype(np.int32), g.rows.astype(np.int32)
    shape = (g.n, g.n)

    def build(fmt):
        if fmt == "coo":
            return formats.build_coo(rows, cols, vals, shape, sr)
        if fmt == "csr":
            return formats.build_csr(rows, cols, vals, shape, sr)
        if fmt == "csc":
            return formats.build_csc(rows, cols, vals, shape, sr)
        if fmt == "bsr":
            return formats.build_bsr_padded(rows, cols, vals, shape, sr, block=(128, 128))
        raise ValueError(fmt)

    a_mv = build(fmt_spmv)
    a_msv = build(fmt_spmspv)
    n_pad = max(getattr(a_mv, "shape", shape)[0], getattr(a_msv, "shape", shape)[0])
    # Bucketed frontiers (TPU adaptation, DESIGN.md §2): XLA needs static
    # shapes, so a single f_max=n frontier would make SpMSpV's work
    # density-independent — the opposite of the paper's point. Instead we
    # compile a small ladder of frontier capacities and lax.switch on the
    # *live* nonzero count; work then tracks density in ~4x steps while the
    # whole traversal stays inside one jit. An explicit f_max pins one rung.
    if f_max:
        buckets = [min(f_max, g.n)]
    else:
        buckets = sorted({max(64, g.n // 16), max(128, g.n // 4), g.n})
    make_fns = functools.partial(_make_fns, shape=shape, n_pad=n_pad, sr=sr,
                                 buckets=tuple(buckets), graph_nnz=g.nnz)
    spmv_fn, spmspv_fn, spmv_batch_fn, spmspv_batch_fn = make_fns(a_mv, a_msv)
    feats = g.features()
    return GraphEngine(
        spmv_fn=spmv_fn,
        spmspv_fn=spmspv_fn,
        n=n_pad,
        n_true=g.n,
        threshold=stump.switch_threshold(feats),
        graph_class=stump.classify(feats),
        sr=sr,
        spmv_batch_fn=spmv_batch_fn,
        spmspv_batch_fn=spmspv_batch_fn,
        mats=(a_mv, a_msv),
        make_fns=make_fns,
    )


def _make_fns(a_mv, a_msv, *, shape, n_pad: int, sr: Semiring,
              buckets: tuple, graph_nnz: int):
    """The engine's four matvec closures over (a_mv, a_msv): single-vector
    SpMV / bucketed SpMSpV and their [B, n]-block counterparts. Reads only
    static structure (shapes, formats, max_col_nnz) from the matrices'
    pytree metadata, so traced matrices work as well as concrete ones."""

    def spmv_fn(x: Array) -> Array:
        xp = _pad(x, a_mv.shape[1], sr)
        return _pad(spmv(a_mv, xp, sr)[: shape[0]], n_pad, sr)

    def msv_at(fmax):
        def fn(x: Array) -> Array:
            f = frontier_from_dense(x[: shape[1]], sr, f_max=fmax)
            y = spmspv(a_msv, f, sr)
            return _pad(y[: shape[0]], n_pad, sr)
        return fn

    branches = [msv_at(b) for b in buckets]

    def spmspv_fn(x: Array) -> Array:
        if len(branches) == 1:
            return branches[0](x)
        nnz = jnp.sum((x[: shape[1]] != sr.zero).astype(jnp.int32))
        sel = jnp.searchsorted(jnp.asarray(buckets, jnp.int32), nnz)
        sel = jnp.minimum(sel, len(buckets) - 1)
        return jax.lax.switch(sel, branches, x)

    # Batched closures. The SpMSpV bucket ladder survives batching as a
    # *scalar* switch: the selected rung's capacity covers every row, so
    # each row's result is the same (lossless) vector the unbatched ladder
    # produces, but only ONE rung executes per iteration — a per-row switch
    # index under vmap would run all of them. CSC engines take the
    # union-frontier path (one shared column gather + one B-lane
    # ⊕-segment-reduce, see core.spmspv.spmspv_batch_union) keyed on the
    # union nonzero count; other formats vmap the per-row closure keyed on
    # the max per-row count.
    if isinstance(a_mv, (formats.COOMatrix, formats.CSRMatrix)):
        def spmv_batch_fn(xs: Array) -> Array:
            xp = _pad_cols(xs, a_mv.shape[1], sr)
            y = spmv_batch(a_mv, xp, sr)[:, : shape[0]]
            return _pad_cols(y, n_pad, sr)
    else:
        spmv_batch_fn = jax.vmap(spmv_fn)
    use_union = isinstance(a_msv, formats.CSCMatrix)

    def msv_batch_at(fmax):
        if not use_union:
            return jax.vmap(msv_at(fmax))
        # Work model (the paper's own selection logic, applied per rung): a
        # capacity-fmax CSC gather touches fmax * max_col_nnz slots; once
        # that exceeds the matrix's nnz, the dense-input SpMV computes the
        # *identical* vector for strictly less work. Union frontiers densify
        # B times faster than single ones, so batched ladders cross over on
        # rungs single-source traversals still run sparse.
        if (fmax * a_msv.max_col_nnz >= graph_nnz
                and isinstance(a_mv, (formats.COOMatrix, formats.CSRMatrix))):
            return spmv_batch_fn

        def fn(xs: Array) -> Array:
            y = spmspv_batch_union(a_msv, xs[:, : shape[1]], sr, f_max=fmax)
            return _pad_cols(y[:, : shape[0]], n_pad, sr)
        return fn

    batch_branches = [msv_batch_at(b) for b in buckets]

    def spmspv_batch_fn(xs: Array) -> Array:
        if len(batch_branches) == 1:
            return batch_branches[0](xs)
        live = xs[:, : shape[1]] != sr.zero
        if use_union:
            nnz = jnp.sum(jnp.any(live, axis=0).astype(jnp.int32))
        else:
            nnz = jnp.max(jnp.sum(live.astype(jnp.int32), axis=1))
        sel = jnp.searchsorted(jnp.asarray(buckets, jnp.int32), nnz)
        sel = jnp.minimum(sel, len(batch_branches) - 1)
        return jax.lax.switch(sel, batch_branches, xs)
    return spmv_fn, spmspv_fn, spmv_batch_fn, spmspv_batch_fn


def calibrate_threshold(engine: GraphEngine, probe_densities=(0.01, 0.05,
                        0.2, 0.5), iters: int = 3) -> float:
    """Hardware-calibrated switch point (beyond-paper, DESIGN.md §8).

    The paper's 20%/50% thresholds encode *UPMEM's* SpMV:SpMSpV cost ratio.
    This measures both kernels on the actual backend at a few densities and
    returns the crossover — on this CPU mesh SpMV tends to win everywhere
    (threshold → 0); on transfer-bound hardware the paper's values emerge."""
    import time

    spmv = jax.jit(engine.spmv_fn)
    spmspv = jax.jit(engine.spmspv_fn)
    rng = np.random.default_rng(0)

    def t(fn, x):
        fn(x).block_until_ready()
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn(x).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    last_spmspv_win = 0.0
    for d in sorted(probe_densities):
        nz = rng.random(engine.n) < d
        if engine.sr.name == "min_plus":
            xv = np.where(nz, rng.random(engine.n), np.inf).astype(np.float32)
        else:
            xv = (nz * rng.random(engine.n)).astype(np.float32)
        x = jnp.asarray(xv, engine.sr.dtype)
        if t(spmspv, x) < t(spmv, x):
            last_spmspv_win = d
    return last_spmspv_win


def _pad(x: Array, n: int, sr: Semiring) -> Array:
    if x.shape[0] == n:
        return x
    if x.shape[0] > n:
        return x[:n]
    return jnp.pad(x, (0, n - x.shape[0]), constant_values=sr.zero)


def _pad_cols(xs: Array, n: int, sr: Semiring) -> Array:
    """[B, m] -> [B, n]: slice or ⊕-zero-pad the trailing axis."""
    if xs.shape[1] == n:
        return xs
    if xs.shape[1] > n:
        return xs[:, :n]
    return jnp.pad(xs, ((0, 0), (0, n - xs.shape[1])),
                   constant_values=sr.zero)


def density_of(x: Array, sr: Semiring, n_true: int) -> Array:
    nz = jnp.sum((x[:n_true] != sr.zero).astype(jnp.int32))
    return nz.astype(jnp.float32) / float(n_true)


def density_of_batch(xs: Array, sr: Semiring, n_true: int) -> Array:
    """Per-row frontier densities of a [B, n] block -> [B] f32."""
    nz = jnp.sum((xs[:, :n_true] != sr.zero).astype(jnp.int32), axis=1)
    return nz.astype(jnp.float32) / float(n_true)

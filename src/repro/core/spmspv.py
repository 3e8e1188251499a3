"""Semiring SpMSpV: y = A ⊕.⊗ x with a **compressed sparse input vector**
(paper §4.1). The frontier (non-zero entries of x) is a static-shape
(indices, values, count) triple so the whole traversal loop stays inside jit.

Three element-level variants mirror the paper's design space:

* ``spmspv_csr_masked``  — CSR/COO style: scan *all* nnz, mask by frontier
  membership (paper's CSR-SpMSpV; uniformly worst, kept for the Fig-5 study).
* ``spmspv_csc_gather``  — CSC style: gather only the active columns' slices
  (the paper's winning family; work ∝ f_max · max_col_nnz).
* ``spmspv_bsr_tiles``   — TPU adaptation: only active *column-tiles* are
  processed (Pallas kernel; jnp oracle in kernels/ref.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.formats import COOMatrix, CSCMatrix, CSRMatrix
from repro.core.semiring import Semiring

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Frontier:
    """Compressed sparse vector: indices [f_max] (pad = n → out of range),
    values [f_max] (pad = semiring zero), count scalar."""

    indices: Array
    values: Array
    count: Array
    n: int

    def tree_flatten(self):
        return (self.indices, self.values, self.count), (self.n,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux[0])

    @property
    def f_max(self) -> int:
        return self.indices.shape[0]

    def density(self) -> Array:
        """Non-zeros / n, in [0,1] — the paper's switching signal (§4.2)."""
        return self.count.astype(jnp.float32) / float(self.n)

    def to_dense(self, sr: Semiring) -> Array:
        dense = jnp.full((self.n,), sr.zero, dtype=sr.dtype)
        ok = self.indices < self.n
        safe = jnp.where(ok, self.indices, 0)
        val = jnp.where(ok, self.values.astype(sr.dtype), sr.zero)
        if sr.collective == "psum":
            return dense.at[safe].add(jnp.where(ok, val, 0))
        if sr.collective == "pmin":
            return dense.at[safe].min(val)
        return dense.at[safe].max(val)


def frontier_from_dense(x: Array, sr: Semiring, f_max: int | None = None) -> Frontier:
    """Compress a dense vector: stable-partition non-zero entries first.
    f_max defaults to n (always lossless); callers size it down for speed."""
    n = x.shape[0]
    f_max = f_max or n
    is_nz = x != sr.zero
    count = jnp.sum(is_nz.astype(jnp.int32))
    # Sort by (not nz) is a stable partition bringing non-zeros to the front.
    order = jnp.argsort(~is_nz, stable=True)
    idx = jnp.where(jnp.arange(n) < count, order, n)[:f_max].astype(jnp.int32)
    vals = jnp.where(idx < n, x[jnp.where(idx < n, idx, 0)], sr.zero)[:f_max]
    return Frontier(idx, vals.astype(sr.dtype), jnp.minimum(count, f_max), n)


def spmspv_csr_masked(a: CSRMatrix, x: Frontier, sr: Semiring) -> Array:
    """Paper's CSR-SpMSpV: touches every stored nonzero, masking inactive
    columns — the reason CSR is 2.8–25× slower in §6.1. Membership test uses
    the dense scatter of the frontier (O(n) setup, O(nnz) scan)."""
    m, n = a.shape
    x_dense = x.to_dense(sr)
    ok = a.seg_ids < m
    xj = x_dense[jnp.where(ok, a.cols, 0)]
    prod = sr.mul(a.vals.astype(sr.dtype), xj)
    prod = jnp.where(ok & (xj != sr.zero), prod, sr.zero)
    return a.reduce_rows(prod[None], sr)[0]


def spmspv_csc_gather(a: CSCMatrix, x: Frontier, sr: Semiring) -> Array:
    """Paper's CSC-SpMSpV: gather only active columns. For each frontier
    entry j, slice column j's (rows, vals) (≤ max_col_nnz entries) and
    ⊕-scatter a_ij ⊗ x_j into y. Work O(f_max · max_col_nnz)."""
    m, n = a.shape
    ok_col = x.indices < n
    safe_j = jnp.where(ok_col, x.indices, 0)
    start = a.col_ptr[safe_j]                     # [f_max]
    length = a.col_ptr[safe_j + 1] - start        # [f_max]
    offs = jnp.arange(a.max_col_nnz, dtype=jnp.int32)  # [L]
    gidx = start[:, None] + offs[None, :]          # [f_max, L]
    in_col = offs[None, :] < length[:, None]
    gidx = jnp.where(in_col, gidx, a.nnz_max - 1)
    rows = a.rows[gidx]                            # [f_max, L]
    vals = a.vals[gidx].astype(sr.dtype)
    prod = sr.mul(vals, x.values.astype(sr.dtype)[:, None])
    valid = in_col & ok_col[:, None]
    prod = jnp.where(valid, prod, sr.zero)
    seg = jnp.where(valid, rows, m)
    return sr.segment_reduce(prod.reshape(-1), seg.reshape(-1), m)


def spmspv_coo_masked(a: COOMatrix, x: Frontier, sr: Semiring) -> Array:
    """Paper's COO-SpMSpV: full nnz scan masked by frontier membership
    (no row grouping → scattered ⊕-updates, Fig 5's baseline variant)."""
    m, n = a.shape
    x_dense = x.to_dense(sr)
    ok = a.rows < m
    xj = x_dense[jnp.where(ok, a.cols, 0)]
    prod = sr.mul(a.vals.astype(sr.dtype), xj)
    prod = jnp.where(ok & (xj != sr.zero), prod, sr.zero)
    return sr.segment_reduce(prod, jnp.where(ok, a.rows, m), m)


def spmspv_batch(a, xs: Array, sr: Semiring, f_max: int | None = None,
                 impl: str = "auto") -> Array:
    """Batched SpMSpV over a [B, n] block of *dense* vectors: each row is
    compressed to a capacity-``f_max`` frontier and multiplied independently.
    Rows compress to different live counts but identical static shapes, so
    one vmapped kernel serves the whole block; a row's result is bit-equal
    to the unbatched spmspv at the same capacity."""

    def one(x: Array) -> Array:
        f = frontier_from_dense(x, sr, f_max=f_max)
        return spmspv(a, f, sr, impl=impl)

    return jax.vmap(one)(xs)


def spmspv_batch_union(a: CSCMatrix, xs: Array, sr: Semiring,
                       f_max: int | None = None) -> Array:
    """Batched CSC SpMSpV over the **union frontier** — the fast path for
    query blocks sharing one graph. All B rows touch the same adjacency, so
    the active-column structure is compressed once across the block:

    * union mask ∨_b (xs[b] != 0) -> one capacity-``f_max`` column list;
    * one [F, L] gather of the columns' (rows, vals) slices, shared by
      every query (the vmapped per-row form gathers it B times);
    * per-row products against xs[:, cols] -> [B, F, L];
    * ONE ⊕-segment-reduce with the [F, L] ids shared across the B lanes
      (data transposed to [F*L, B]) instead of B scattered reductions.

    A row contributes only where its own entry is nonzero, so row b's
    result equals spmspv(a, frontier(xs[b])) whenever ``f_max`` covers the
    union (⊕-reduction order may differ, which matters only below float
    tolerance for ⟨+,×⟩). Work is O(f_union · max_col_nnz · B) products but
    the expensive gather/scatter structure is batch-invariant.

    Runs under the ``spmspv_union`` name scope: the union compaction
    under ``frontier``, the column and input gathers under ``gather``."""
    with jax.named_scope("spmspv_union"):
        m, n = a.shape
        b = xs.shape[0]
        f_max = f_max or n
        with jax.named_scope("frontier"):
            nz_any = jnp.any(xs != sr.zero, axis=0)                 # [n]
            count = jnp.sum(nz_any.astype(jnp.int32))
            order = jnp.argsort(~nz_any, stable=True)
            idx = jnp.where(jnp.arange(n) < count, order, n)[:f_max].astype(jnp.int32)
        with jax.named_scope("gather"):
            ok_col = idx < n
            safe_j = jnp.where(ok_col, idx, 0)
            start = a.col_ptr[safe_j]                               # [F]
            length = a.col_ptr[safe_j + 1] - start
            offs = jnp.arange(a.max_col_nnz, dtype=jnp.int32)       # [L]
            gidx = start[:, None] + offs[None, :]                   # [F, L]
            in_col = offs[None, :] < length[:, None]
            gidx = jnp.where(in_col, gidx, a.nnz_max - 1)
            rows = a.rows[gidx]                                     # [F, L]
            vals = a.vals[gidx].astype(sr.dtype)
            xv = jnp.where(ok_col[None, :], xs[:, safe_j].astype(sr.dtype),
                           sr.zero)                                 # [B, F]
        prod = sr.mul(vals[None], xv[:, :, None])                   # [B, F, L]
        valid = in_col[None] & (xv[:, :, None] != sr.zero)
        prod = jnp.where(valid, prod, sr.zero)
        seg = jnp.where(in_col, rows, m)                            # [F, L] shared
        flat = prod.reshape(b, -1).T                                # [F*L, B]
        y = sr.segment_reduce(flat, seg.reshape(-1), m)             # [m, B]
        return y.T


def spmspv(a, x: Frontier, sr: Semiring, impl: str = "auto") -> Array:
    if isinstance(a, COOMatrix):
        return spmspv_coo_masked(a, x, sr)
    if isinstance(a, CSRMatrix):
        return spmspv_csr_masked(a, x, sr)
    if isinstance(a, CSCMatrix):
        return spmspv_csc_gather(a, x, sr)
    from repro.core.formats import PaddedBSR

    if isinstance(a, PaddedBSR):
        from repro.kernels import ops

        if impl == "ref":
            return ops.semiring_spmspv_ref(a, x, sr)
        if impl == "fused":
            return ops.semiring_spmspv_fused(a, x, sr)
        return ops.semiring_spmspv(a, x, sr)
    raise TypeError(type(a))

"""Semiring SpMV: y = A ⊕.⊗ x with a dense input vector (paper §3).

Element-format variants (COO/CSR) run as fully vectorized gather +
⊕-segment-reduce — the realistic CPU/TPU-VPU formulation. The BSR variant
dispatches to the Pallas MXU kernel (kernels/semiring_spmv.py) and is the
TPU hot path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.formats import BSRMatrix, COOMatrix, CSRMatrix
from repro.core.semiring import Semiring

Array = jax.Array


def spmv_coo(a: COOMatrix, x: Array, sr: Semiring) -> Array:
    """y_i = ⊕_{(i,j)∈A} a_ij ⊗ x_j. Padded entries have row=M → dropped by
    the out-of-range scatter, matching the paper's padded equal-size tiles."""
    m, n = a.shape
    ok = a.rows < m
    xj = x[jnp.where(ok, a.cols, 0)]
    prod = sr.mul(a.vals.astype(sr.dtype), xj.astype(sr.dtype))
    prod = jnp.where(ok, prod, sr.zero)
    return sr.segment_reduce(prod, jnp.where(ok, a.rows, m), m)


def spmv_csr(a: CSRMatrix, x: Array, sr: Semiring) -> Array:
    """CSR uses the precomputed expanded segment ids; identical math to COO
    but entries are row-sorted, so the ⊕-reduce can be a segmented scan
    read at each row's end (``CSRMatrix.reduce_rows``), not a scatter."""
    m, n = a.shape
    ok = a.seg_ids < m
    xj = x[jnp.where(ok, a.cols, 0)]
    prod = sr.mul(a.vals.astype(sr.dtype), xj.astype(sr.dtype))
    prod = jnp.where(ok, prod, sr.zero)
    return a.reduce_rows(prod[None], sr)[0]


def spmv_bsr_ref(a: BSRMatrix, x: Array, sr: Semiring) -> Array:
    """Pure-jnp oracle for the Pallas BSR kernel: scan over the padded tile
    list, ⊕-accumulate each tile's dense matvec into its block row."""
    bm, bn = a.block
    mb = a.n_block_rows
    x_tiles = x.reshape(-1, bn)

    # Expand tile→block-row mapping from tile_row_ptr (static t_max).
    t_idx = jnp.arange(a.t_max, dtype=jnp.int32)
    tile_brow = jnp.searchsorted(a.tile_row_ptr[1:], t_idx, side="right").astype(jnp.int32)
    n_real = a.tile_row_ptr[-1]
    valid = t_idx < n_real

    def body(y, inp):
        tile, tcol, brow, ok = inp
        xb = x_tiles[tcol].astype(sr.dtype)
        contrib = sr.add_reduce(sr.mul(tile.astype(sr.dtype), xb[None, :]), axis=1)
        contrib = jnp.where(ok, contrib, sr.zero)
        row_val = sr.add(y[brow], contrib)
        return y.at[brow].set(jnp.where(ok, row_val, y[brow])), ()

    y0 = jnp.full((mb, bm), sr.zero, dtype=sr.dtype)
    y, _ = jax.lax.scan(body, y0, (a.tiles, a.tile_cols, tile_brow, valid))
    return y.reshape(-1)


def spmv_batch(a, xs: Array, sr: Semiring, impl: str = "auto") -> Array:
    """Batched SpMV: Y = A ⊕.⊗ Xᵀ with a [B, n] block of dense input vectors
    (multi-query traversal, §4 many-source regime). Element formats share
    one segment-id vector across the block, so the whole batch reduces in
    one pass: CSR by ``CSRMatrix.reduce_rows`` (the row-sorted scan on the
    [B, nnz] products, or a float sum's scatter), COO, whose order is not
    promised, with a B-lane ⊕-scatter (data transposed to [nnz, B]) — a
    vmapped per-row scatter would serialize. Other formats fall back to
    vmap.
    Runs under the ``spmv_batch`` name scope, its input gather under
    ``gather``."""
    with jax.named_scope("spmv_batch"):
        if isinstance(a, (COOMatrix, CSRMatrix)):
            m, n = a.shape
            csr = isinstance(a, CSRMatrix)
            seg = a.seg_ids if csr else a.rows
            ok = seg < m
            with jax.named_scope("gather"):
                xj = xs[:, jnp.where(ok, a.cols, 0)]           # [B, nnz]
            prod = sr.mul(a.vals.astype(sr.dtype)[None], xj.astype(sr.dtype))
            prod = jnp.where(ok[None], prod, sr.zero)
            if csr:
                return a.reduce_rows(prod, sr)
            return sr.segment_reduce(prod.T, jnp.where(ok, seg, m), m).T
        return jax.vmap(lambda x: spmv(a, x, sr, impl=impl))(xs)


def spmv(a, x: Array, sr: Semiring, impl: str = "auto") -> Array:
    from repro.core.formats import PaddedBSR  # deferred: avoid import cycle

    if isinstance(a, COOMatrix):
        return spmv_coo(a, x, sr)
    if isinstance(a, CSRMatrix):
        return spmv_csr(a, x, sr)
    if isinstance(a, BSRMatrix):
        return spmv_bsr_ref(a, x, sr)
    if isinstance(a, PaddedBSR):
        from repro.kernels import ops  # deferred: kernels import pallas

        if impl == "ref":
            return ops.semiring_spmv_ref(a, x, sr)
        if impl == "fused":
            return ops.semiring_spmv_fused(a, x, sr)
        return ops.semiring_spmv(a, x, sr)
    raise TypeError(type(a))

"""Pallas TPU kernel: semiring block-sparse (BSR) SpMV.

TPU adaptation of the paper's CSC/CSR element kernels (DESIGN.md §2):
UPMEM DPUs chase per-column pointers with a scalar core; the TPU MXU/VPU
wants dense (bm, bn) tiles. The sparse structure therefore lives at *tile*
granularity — CSR-of-tiles metadata drives a scalar-prefetched BlockSpec
index map, so only stored tiles are DMA'd HBM→VMEM (the WRAM staging step
of §4.1.3, with BlockSpec playing the role of the DPU's DMA engine).

Layout (produced by ops.bsr_to_padded):
    tiles     f32/i32 [mb, T, bm, bn]   ELL-of-tiles, padded with ⊕-identity tiles
    tile_cols i32     [mb, T]           tile-column index (pad: 0, payload is identity)
    x         [1, nb * bn]              dense input vector, one lane-major row
    y         [1, mb * bm]              output, one lane-major row

Vectors travel as [1, n] rows so every x/y block is a (1, 128k) slice the
TPU lowering accepts (a 1-D (bn,) block is refused: its layout does not
match the rank-1 XLA tiling). Callers pass and receive flat vectors; the
row reshape is free.

Grid (mb, T): for each block row i, sequentially ⊕-accumulate tile j's dense
matvec into y block i. ⟨+,×⟩ is an x·aᵀ matmul on the MXU; ⟨min,+⟩ / ⟨∨,∧⟩
use VPU elementwise + reduce. Accumulation across the T grid dim revisits the
same output block, the standard TPU reduction pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import Semiring


def _tile_contrib(a, xb, sr: Semiring, out_dtype):
    """One tile's ⊕.⊗ contribution: a [bm, bn] against the x row block
    xb [1, bn] -> [1, bm]. ⟨+,×⟩ is an x·aᵀ matmul on the MXU; the other
    semirings broadcast ⊗ on the VPU, transpose the [bm, bn] product and
    ⊕-reduce over sublanes, so the result lands lane-major like y."""
    if sr.mxu_eligible:
        return jax.lax.dot_general(
            xb, a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(out_dtype)
    return sr.add_reduce(sr.mul(a, xb).T, axis=0, keepdims=True)


def _kernel(cols_ref, tiles_ref, x_ref, y_ref, *, sr: Semiring):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.full_like(y_ref, sr.zero)

    contrib = _tile_contrib(tiles_ref[0, 0], x_ref[...], sr, y_ref.dtype)
    y_ref[...] = sr.add(y_ref[...], contrib)


@functools.partial(jax.jit, static_argnames=("sr", "interpret"))
def semiring_spmv_padded(tiles, tile_cols, x, *, sr: Semiring, interpret: bool):
    """y = A ⊕.⊗ x over the padded ELL-of-tiles layout."""
    mb, t_grid, bm, bn = tiles.shape
    grid = (mb, t_grid)

    y = pl.pallas_call(
        functools.partial(_kernel, sr=sr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # tile payload: one (bm, bn) tile per step
                pl.BlockSpec((1, 1, bm, bn), lambda i, j, cols: (i, j, 0, 0)),
                # x block selected by the scalar-prefetched tile-column index
                pl.BlockSpec((1, bn), lambda i, j, cols: (0, cols[i, j])),
            ],
            out_specs=pl.BlockSpec((1, bm), lambda i, j, cols: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, mb * bm), x.dtype),
        interpret=interpret,
    )(tile_cols, tiles, x.reshape(1, -1))
    return y.reshape(-1)


# ---------------------------------------------------------------------------
# Fused Load+Kernel: double-buffered DMA streaming (ISSUE 9 tentpole).
#
# The unfused kernel above lets the BlockSpec pipeline DMA whole-slot rows —
# every grid step moves a tile whether it is payload or ⊕-identity pad.  The
# fused variants below keep the adjacency in ANY (compiler-placed, HBM on
# TPU) memory and stream only *real* tiles through a two-slot VMEM scratch
# window: tile t+1's async copy is issued before tile t's compute runs — the
# paper's "improved DMA engines with non-blocking capabilities" realized
# inside the kernel rather than between phases.  Contributions are reduced
# in the same per-slot order as the unfused kernel and skipped slots are
# exact ⊕-identities, so results are bit-identical.
# ---------------------------------------------------------------------------


def _stream_row(tiles_at, col_at, x_ref, n_real, *, sr: Semiring,
                bm: int, bn: int, dtype):
    """Shared double-buffered streaming loop: DMA tile ``j+1`` into the free
    scratch slot while tile ``j`` computes; ⊕-fold contributions into a
    carried accumulator.  ``tiles_at(j)``/``col_at(j)`` abstract the layout
    (ELL [i, j] vs sliced-ELL [base + j] vs SpMSpV's permuted slots)."""

    def body(scratch, sems):
        def get_dma(slot, j):
            return pltpu.make_async_copy(tiles_at(j), scratch.at[slot], sems.at[slot])

        @pl.when(n_real > 0)
        def _warmup():
            get_dma(0, 0).start()

        def loop(j, acc):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_real)
            def _prefetch():
                get_dma(jax.lax.rem(j + 1, 2), j + 1).start()

            get_dma(slot, j).wait()
            a = scratch[slot]
            # the x row is whole in VMEM; each tile reads its aligned
            # (1, bn) window of it
            start = pl.multiple_of(col_at(j) * bn, bn)
            xb = x_ref[:, pl.ds(start, bn)]
            return sr.add(acc, _tile_contrib(a, xb, sr, acc.dtype))

        acc0 = jnp.full((1, bm), sr.zero, dtype)
        return jax.lax.fori_loop(0, n_real, loop, acc0)

    return pl.run_scoped(
        body,
        scratch=pltpu.VMEM((2, bm, bn), dtype),
        sems=pltpu.SemaphoreType.DMA((2,)),
    )


def _fused_kernel(meta_ref, tiles_ref, x_ref, y_ref, *, sr: Semiring,
                  bm: int, bn: int, dtype):
    i = pl.program_id(0)
    n_real = meta_ref[i, 0]
    y_ref[...] = _stream_row(lambda j: tiles_ref.at[i, j],
                             lambda j: meta_ref[i, 1 + j],
                             x_ref, n_real, sr=sr, bm=bm, bn=bn, dtype=dtype)


def _row_specs(x, mb: int, bm: int, out_block):
    """Whole-x input spec and per-block-row output spec over [1, n] rows;
    ``out_block(i, *prefetch)`` picks the output block (the Retrieve-side
    row permutation for sell-C-σ)."""
    x_spec = pl.BlockSpec((1, x.shape[0]), lambda i, *pref: (0, 0))
    y_spec = pl.BlockSpec((1, bm), lambda i, *pref: (0, out_block(i, *pref)))
    return x_spec, y_spec, jax.ShapeDtypeStruct((1, mb * bm), x.dtype)


def _unrow(y, chunks: int | None):
    """[1, mb·bm] kernel output -> flat [mb·bm], or chunk-major
    [chunks, m_per] — the layout collectives.merge_chunks consumes. Chunk c
    holds block rows [c·mb/chunks, (c+1)·mb/chunks), so chunk-major is the
    flat order and the reshape moves no data."""
    if chunks is None:
        return y.reshape(-1)
    assert y.shape[1] % chunks == 0, f"chunks={chunks} must divide {y.shape}"
    return y.reshape(chunks, -1)


@functools.partial(jax.jit, static_argnames=("sr", "interpret", "chunks"))
def semiring_spmv_fused_padded(tiles, meta, x, *, sr: Semiring,
                               interpret: bool,
                               chunks: int | None = None):
    """Fused Load+Kernel SpMV: meta int32 [mb, 1+T] = (n_real | tile_cols).
    Streams only the first n_real slots of each block row through the
    double-buffered scratch; bit-identical to semiring_spmv_padded."""
    mb, t_grid, bm, bn = tiles.shape
    x_spec, y_spec, out_shape = _row_specs(x, mb, bm, lambda i, meta: i)
    y = pl.pallas_call(
        functools.partial(_fused_kernel, sr=sr, bm=bm, bn=bn, dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mb,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # tiles stay in HBM
                x_spec,
            ],
            out_specs=y_spec,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(meta, tiles, x.reshape(1, -1))
    return _unrow(y, chunks)


def _sell_kernel(meta_ref, cols_ref, tiles_ref, x_ref, y_ref, *, sr: Semiring,
                 bm: int, bn: int, dtype):
    i = pl.program_id(0)
    base = meta_ref[i, 1]
    n_real = meta_ref[i, 2]
    y_ref[...] = _stream_row(lambda j: tiles_ref.at[base + j],
                             lambda j: cols_ref[base + j],
                             x_ref, n_real, sr=sr, bm=bm, bn=bn, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("sr", "interpret", "chunks"))
def semiring_spmv_sell(tiles, tile_cols, row_meta, x, *, sr: Semiring,
                       interpret: bool, chunks: int | None = None):
    """Fused Load+Kernel SpMV over the sliced-ELL (sell-C-σ) layout: tiles
    flat [slot_total, bm, bn]; row_meta [mb, 3] = (out_block, base, n_real)
    in compute order.  The output BlockSpec applies the row permutation
    (Retrieve-side scatter), so y comes back in original row order."""
    _, bm, bn = tiles.shape
    mb = row_meta.shape[0]
    x_spec, y_spec, out_shape = _row_specs(
        x, mb, bm, lambda i, meta, cols: meta[i, 0])
    y = pl.pallas_call(
        functools.partial(_sell_kernel, sr=sr, bm=bm, bn=bn, dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(mb,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                x_spec,
            ],
            out_specs=y_spec,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(row_meta, tile_cols, tiles, x.reshape(1, -1))
    return _unrow(y, chunks)

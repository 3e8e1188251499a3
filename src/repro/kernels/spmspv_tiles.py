"""Pallas TPU kernel: frontier-filtered semiring BSR SpMSpV.

The paper's CSC-SpMSpV skips matrix columns whose index is absent from the
sparse input vector (§4.1). The TPU-granular analogue skips *column tiles*
with no active frontier entry:

* ops.py computes, per block row, a permutation that compacts slots holding
  active tiles to the front (a jnp argsort over the prefetched metadata
  only — tile payloads are never moved), plus ``n_active[i]``.
* The BlockSpec index map indirects through the permutation, so only active
  tiles are streamed HBM→VMEM; masked-out steps re-read an already-resident
  slot instead of issuing a dead DMA — the same work-skipping UPMEM's DPU
  gets by not issuing the inactive column's DMA (§4.1.3).
* The kernel masks compute with ``pl.when(j < n_active[i])``.
* x enters densified as one [1, nb*bn] row (the same row layout as
  semiring_spmv.py); inactive x blocks are never indexed.

meta layout (scalar-prefetched, int32 [mb, 1 + 2T]):
    meta[i, 0]         = n_active_i
    meta[i, 1 : 1+T]   = slot permutation (active slots first)
    meta[i, 1+T : ]    = tile-column index per *permuted* slot
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import Semiring
from repro.kernels.semiring_spmv import (
    _row_specs, _stream_row, _tile_contrib, _unrow,
)


def _kernel(meta_ref, tiles_ref, x_ref, y_ref, *, sr: Semiring):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.full_like(y_ref, sr.zero)

    i = pl.program_id(0)
    n_active = meta_ref[i, 0]

    @pl.when(j < n_active)
    def _compute():
        contrib = _tile_contrib(tiles_ref[0, 0], x_ref[...], sr, y_ref.dtype)
        y_ref[...] = sr.add(y_ref[...], contrib)


@functools.partial(jax.jit, static_argnames=("sr", "interpret"))
def semiring_spmspv_padded(tiles, meta, x, *, sr: Semiring, interpret: bool):
    """tiles [mb, T, bm, bn] (unpermuted ELL-of-tiles); meta as above;
    x densified [nb*bn]."""
    mb, t_grid, bm, bn = tiles.shape

    def _tile_map(i, j, meta):
        ok = j < meta[i, 0]
        slot = jnp.where(ok, meta[i, 1 + j], meta[i, 1])
        return (i, slot, 0, 0)

    def _x_map(i, j, meta):
        ok = j < meta[i, 0]
        return (0, jnp.where(ok, meta[i, 1 + t_grid + j], meta[i, 1 + t_grid]))

    y = pl.pallas_call(
        functools.partial(_kernel, sr=sr),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mb, t_grid),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bn), _tile_map),
                pl.BlockSpec((1, bn), _x_map),
            ],
            out_specs=pl.BlockSpec((1, bm), lambda i, j, meta: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, mb * bm), x.dtype),
        interpret=interpret,
    )(meta, tiles, x.reshape(1, -1))
    return y.reshape(-1)


def _fused_kernel(meta_ref, tiles_ref, x_ref, y_ref, *, sr: Semiring,
                  bm: int, bn: int, t_grid: int, dtype):
    i = pl.program_id(0)
    n_active = meta_ref[i, 0]
    y_ref[...] = _stream_row(lambda j: tiles_ref.at[i, meta_ref[i, 1 + j]],
                             lambda j: meta_ref[i, 1 + t_grid + j],
                             x_ref, n_active, sr=sr, bm=bm, bn=bn, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("sr", "interpret", "chunks"))
def semiring_spmspv_fused_padded(tiles, meta, x, *, sr: Semiring,
                                 interpret: bool,
                                 chunks: int | None = None):
    """Fused Load+Kernel SpMSpV: same meta layout as the unfused kernel, but
    the adjacency stays in ANY/HBM and only frontier-active slots are DMA'd
    through the double-buffered scratch (inactive slots issue *no* copy at
    all, vs the unfused kernel's masked re-read of a resident slot).
    Bit-identical to semiring_spmspv_padded."""
    mb, t_grid, bm, bn = tiles.shape
    x_spec, y_spec, out_shape = _row_specs(x, mb, bm, lambda i, meta: i)
    y = pl.pallas_call(
        functools.partial(_fused_kernel, sr=sr, bm=bm, bn=bn, t_grid=t_grid,
                          dtype=x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mb,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                x_spec,
            ],
            out_specs=y_spec,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(meta, tiles, x.reshape(1, -1))
    return _unrow(y, chunks)

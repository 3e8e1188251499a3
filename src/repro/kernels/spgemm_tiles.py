"""Pallas TPU kernel: masked semiring block-sparse SpGEMM.

C = (A ⊕.⊗ B) ⊙ M with A in the ELL-of-tiles layout (PaddedBSR), B dense
[K, N], M a dense structural mask over [M, N]. This is the matrix-matrix
sibling of kernels/semiring_spmv.py: the same scalar-prefetched BlockSpec
indirection streams only *stored* A tiles HBM→VMEM, and a second prefetched
table marks which output tiles have any mask entry, so fully-masked output
tiles skip their compute entirely — the GraphBLAS masked-SpGEMM
work-skipping (triangle counting's L·Lᵀ⊙L touches only edge tiles) at the
granularity the MXU wants.

Layout:
    tiles [mb, T, bm, bk]   A's ELL-of-tiles (pad slots hold ⊕-identity)
    meta  [mb, T + nb] i32  meta[i, :T] = A tile-columns,
                            meta[i, T+j] = 1 iff mask tile (i, j) is nonempty
    b     [kb*bk, nb*bn]    dense right operand
    mask  [mb*bm, nb*bn]    structural mask (≠ ⊕-identity ⇒ keep)
    out   [mb*bm, nb*bn]

Grid (mb, nb, T): t innermost ⊕-accumulates A tile (i, t) × B block
(cols[i,t], j) into output tile (i, j); the final t step applies the mask.
⟨+,×⟩ lowers to jnp.dot on the MXU (sr.mxu_eligible); every other semiring
takes the VPU broadcast-⊗ + ⊕-reduce path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import Semiring


def _kernel(meta_ref, tiles_ref, b_ref, mask_ref, o_ref, *, sr: Semiring,
            t_grid: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, sr.zero)

    out_active = meta_ref[i, t_grid + j] > 0

    @pl.when(out_active)
    def _compute():
        a = tiles_ref[0, 0]          # [bm, bk]
        bb = b_ref[...]              # [bk, bn]
        if sr.mxu_eligible:
            contrib = jnp.dot(a, bb,
                              preferred_element_type=jnp.float32).astype(o_ref.dtype)
        else:
            contrib = sr.add_reduce(sr.mul(a[:, :, None], bb[None]), axis=1)
        o_ref[...] = sr.add(o_ref[...], contrib)

    @pl.when(t == t_grid - 1)
    def _mask():
        o_ref[...] = jnp.where(mask_ref[...] != sr.zero, o_ref[...],
                               jnp.full_like(o_ref, sr.zero))


@functools.partial(jax.jit, static_argnames=("sr", "bn", "interpret"))
def semiring_spgemm_padded(tiles, meta, b, mask, *, sr: Semiring, bn: int,
                           interpret: bool):
    """C = (A ⊕.⊗ B) ⊙ mask over the padded ELL-of-tiles layout. ``bn`` is
    the output tile width; b/mask column counts must be bn-multiples."""
    mb, t_grid, bm, bk = tiles.shape
    n = b.shape[1]
    nb = n // bn
    assert nb * bn == n and mask.shape == (mb * bm, n), (tiles.shape, b.shape,
                                                         mask.shape)

    return pl.pallas_call(
        functools.partial(_kernel, sr=sr, t_grid=t_grid),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mb, nb, t_grid),
            in_specs=[
                # A tile payload: one (bm, bk) tile per t step
                pl.BlockSpec((1, 1, bm, bk), lambda i, j, t, meta: (i, t, 0, 0)),
                # B block selected by the prefetched A tile-column index
                pl.BlockSpec((bk, bn), lambda i, j, t, meta: (meta[i, t], j)),
                # mask tile for this output block
                pl.BlockSpec((bm, bn), lambda i, j, t, meta: (i, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, t, meta: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((mb * bm, n), b.dtype),
        interpret=interpret,
    )(meta, tiles, b, mask)

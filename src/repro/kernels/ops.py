"""Public jit'd wrappers for the Pallas kernels.

This module is the one place that decides interpret mode: every wrapper
takes ``interpret=None`` and resolves it through :func:`interpret_mode`,
which is True off-TPU (tests, CPU benches and the distributed engine run
the *kernel body* in the Pallas interpreter) and False on a TPU backend,
where the kernels compile to Mosaic. The kernel entry points themselves
have no default, so nothing below this layer picks a mode silently.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from repro.core.formats import PaddedBSR, SlicedELL
from repro.core.semiring import Semiring
from repro.core.spmspv import Frontier
from repro.kernels import ref
from repro.kernels.semiring_spmv import (
    semiring_spmv_fused_padded, semiring_spmv_padded, semiring_spmv_sell,
)
from repro.kernels.spgemm_tiles import semiring_spgemm_padded
from repro.kernels.spmspv_tiles import (
    semiring_spmspv_fused_padded, semiring_spmspv_padded,
)

Array = jax.Array


def interpret_mode(interpret: bool | None = None) -> bool:
    """An explicit choice wins; otherwise interpret exactly when the
    default backend is not a TPU. Asked at call time, not at import, so
    importing the kernels never initialises a backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def semiring_spmv(a: PaddedBSR, x: Array, sr: Semiring,
                  interpret: bool | None = None) -> Array:
    """y = A ⊕.⊗ x (dense x). x length must be a.shape[1] (padded)."""
    assert x.shape[0] == a.shape[1], (x.shape, a.shape)
    itp = interpret_mode(interpret)
    return semiring_spmv_padded(a.tiles, a.tile_cols, x.astype(sr.dtype),
                                sr=sr, interpret=itp)


def _ell_n_real(tile_cols: Array) -> Array:
    """Real (non-pad) slot count per block row, from metadata alone: the
    builder stores real tiles first in strictly increasing tile-col order
    and pad slots repeat tile-col 0, so n_real = 1 + #strict increases.
    Rows with zero real tiles come out as 1 — the streamed slot is an
    ⊕-identity pad, so the fused result is unchanged."""
    cols = tile_cols
    return (1 + jnp.sum(cols[:, 1:] > cols[:, :-1], axis=1)).astype(jnp.int32)


def _spmv_fused_meta(a: PaddedBSR) -> Array:
    """int32 [mb, 1+T] = (n_real | tile_cols) for the fused SpMV kernel."""
    return jnp.concatenate([_ell_n_real(a.tile_cols)[:, None], a.tile_cols],
                           axis=1)


def semiring_spmv_fused(a: PaddedBSR, x: Array, sr: Semiring,
                        interpret: bool | None = None,
                        chunks: int | None = None) -> Array:
    """Fused Load+Kernel SpMV (double-buffered DMA over real slots only).
    Bit-identical to semiring_spmv; with ``chunks=d`` the output comes back
    chunk-major [d, m/d] for collectives.merge_chunks."""
    assert x.shape[0] == a.shape[1], (x.shape, a.shape)
    itp = interpret_mode(interpret)
    return semiring_spmv_fused_padded(a.tiles, _spmv_fused_meta(a),
                                      x.astype(sr.dtype), sr=sr,
                                      interpret=itp, chunks=chunks)


def semiring_spmv_sliced(s: SlicedELL, x: Array, sr: Semiring,
                         interpret: bool | None = None,
                         chunks: int | None = None) -> Array:
    """Fused SpMV over the sell-C-σ layout (hub-skew pad collapse)."""
    assert x.shape[0] == s.shape[1], (x.shape, s.shape)
    itp = interpret_mode(interpret)
    return semiring_spmv_sell(s.tiles, s.tile_cols, s.row_meta,
                              x.astype(sr.dtype), sr=sr, interpret=itp,
                              chunks=chunks)


def _spmspv_meta(a: PaddedBSR, f: Frontier, sr: Semiring) -> Array:
    """Build the scalar-prefetch metadata: per block row, compact the slots
    whose tile-column is frontier-active to the front. Pure jnp (runs under
    jit); only metadata moves, never tile payloads."""
    mb, t = a.tile_cols.shape
    bn = a.block[1]
    nb = a.shape[1] // bn
    # Active tile-columns from frontier indices (pad index n → dropped).
    active_cols = jnp.zeros((nb,), jnp.bool_)
    tile_idx = jnp.where(f.indices < f.n, f.indices // bn, nb)
    active_cols = active_cols.at[tile_idx].set(True, mode="drop")
    slot_active = active_cols[a.tile_cols]  # [mb, T]
    # Padded slots hold identity tiles; they may alias tile-col 0 but are
    # harmless (identity contribution) — no need to exclude them.
    perm = jnp.argsort(~slot_active, axis=1, stable=True).astype(jnp.int32)
    n_active = jnp.sum(slot_active, axis=1, dtype=jnp.int32)
    cols_perm = jnp.take_along_axis(a.tile_cols, perm, axis=1)
    return jnp.concatenate([n_active[:, None], perm, cols_perm], axis=1)


def semiring_spmspv(a: PaddedBSR, f: Frontier, sr: Semiring,
                    interpret: bool | None = None) -> Array:
    """y = A ⊕.⊗ x with x given as a sparse Frontier. Only active column
    tiles are streamed (the paper's CSC-SpMSpV work-skipping, at tile
    granularity)."""
    itp = interpret_mode(interpret)
    meta = _spmspv_meta(a, f, sr)
    x_dense = f.to_dense(sr)
    pad = a.shape[1] - x_dense.shape[0]
    if pad:
        x_dense = jnp.pad(x_dense, (0, pad), constant_values=sr.zero)
    return semiring_spmspv_padded(a.tiles, meta, x_dense, sr=sr, interpret=itp)


def semiring_spmspv_fused(a: PaddedBSR, f: Frontier, sr: Semiring,
                          interpret: bool | None = None,
                          chunks: int | None = None) -> Array:
    """Fused Load+Kernel SpMSpV: only frontier-active slots are DMA'd
    through the double-buffered scratch. Bit-identical to semiring_spmspv."""
    itp = interpret_mode(interpret)
    meta = _spmspv_meta(a, f, sr)
    x_dense = f.to_dense(sr)
    pad = a.shape[1] - x_dense.shape[0]
    if pad:
        x_dense = jnp.pad(x_dense, (0, pad), constant_values=sr.zero)
    return semiring_spmspv_fused_padded(a.tiles, meta, x_dense, sr=sr,
                                        interpret=itp, chunks=chunks)


# ---------------------------------------------------------------------------
# Deterministic bytes-moved accounting for the roofline gate.
#
# DMA counts are derived from the *same metadata that drives the kernels'
# index maps and pl.when conditions* (not from timers), in the spirit of the
# bytes-on-wire pricing in graphs/cost_model.py: the unfused BlockSpec
# pipeline issues a copy whenever a block index changes between consecutive
# grid steps (Pallas revisiting rule); the fused kernels issue exactly the
# copies they start.  "Useful" ops count one ⊗ and one ⊕ per element of
# every *real* slot — identical for fused and unfused, so arithmetic
# intensity ratios reduce to measured bytes ratios.
# ---------------------------------------------------------------------------


def _block_changes(idx: np.ndarray) -> int:
    """#DMAs for a sequence of per-step block indices [steps, k]: one for
    the first step plus one per consecutive change."""
    if idx.shape[0] == 0:
        return 0
    return 1 + int(np.any(idx[1:] != idx[:-1], axis=1).sum())


def _stream_stats(tile_dmas_unfused: int, x_dmas_unfused: int,
                  tile_dmas_fused: int, x_elems_fused: int,
                  real_slots: int, mb: int, block, esize: int) -> dict:
    bm, bn = block
    tile_b = bm * bn * esize
    y_b = mb * bm * esize
    ops = 2 * real_slots * bm * bn
    unfused_b = tile_dmas_unfused * tile_b + x_dmas_unfused * bn * esize + y_b
    fused_b = tile_dmas_fused * tile_b + x_elems_fused * esize + y_b
    return {
        "ops": ops,
        "unfused_bytes": unfused_b,
        "fused_bytes": fused_b,
        "unfused_ai": ops / max(1, unfused_b),
        "fused_ai": ops / max(1, fused_b),
        "bytes_saved": unfused_b - fused_b,
    }


def spmv_stream_stats(a: PaddedBSR) -> dict:
    """Bytes moved by unfused vs fused SpMV over this ELL-of-tiles matrix."""
    mb, t = a.tile_cols.shape
    esize = np.dtype(a.tiles.dtype).itemsize
    cols = np.asarray(a.tile_cols)
    n_real = np.asarray(_ell_n_real(a.tile_cols))
    # unfused: grid (mb, T) — tile block index (i, j) changes every step;
    # x block index is cols[i, j] flattened in grid order
    tile_dmas_unf = mb * t
    x_dmas_unf = _block_changes(cols.reshape(-1, 1))
    return _stream_stats(tile_dmas_unf, x_dmas_unf, int(n_real.sum()),
                         a.shape[1] // a.block[1] * a.block[1],
                         int(n_real.sum()), mb, a.block, esize)


def sell_stream_stats(s: SlicedELL, a: PaddedBSR) -> dict:
    """Fused sell-C-σ vs the *unfused ELL* ancestor (same edge list)."""
    mb, t = a.tile_cols.shape
    esize = np.dtype(s.tiles.dtype).itemsize
    cols = np.asarray(a.tile_cols)
    real = int(np.asarray(s.row_meta)[:, 2].sum())
    tile_dmas_unf = mb * t
    x_dmas_unf = _block_changes(cols.reshape(-1, 1))
    return _stream_stats(tile_dmas_unf, x_dmas_unf, real, s.shape[1],
                         real, mb, s.block, esize)


def spmspv_stream_stats(a: PaddedBSR, f: Frontier, sr: Semiring) -> dict:
    """Bytes moved by unfused vs fused SpMSpV for this frontier.  The
    unfused kernel's masked steps re-read a resident slot (index map
    repeats meta[i, 1]), so its tile DMAs follow the block-change rule on
    the permuted slot sequence, not the raw grid size."""
    mb, t = a.tile_cols.shape
    esize = np.dtype(a.tiles.dtype).itemsize
    meta = np.asarray(_spmspv_meta(a, f, sr))
    n_active = meta[:, 0]
    perm, cols_p = meta[:, 1:1 + t], meta[:, 1 + t:]
    j = np.arange(t)[None, :]
    ok = j < n_active[:, None]
    # unfused index maps: slot = perm[i, j] if active else perm[i, 0];
    # x block = cols_p[i, j] if active else cols_p[i, 0]
    slot_seq = np.where(ok, perm, perm[:, :1])
    tile_idx = np.stack([np.repeat(np.arange(mb), t), slot_seq.reshape(-1)], 1)
    x_seq = np.where(ok, cols_p, cols_p[:, :1]).reshape(-1, 1)
    return _stream_stats(_block_changes(tile_idx), _block_changes(x_seq),
                         int(n_active.sum()), a.shape[1],
                         int(n_active.sum()), mb, a.block, esize)


def _spgemm_operands(a: PaddedBSR, b: Array, sr: Semiring,
                     mask: Array | None):
    """Pad B/mask to the kernel's block grid and build the prefetch meta.
    B's column pad uses the ⊗-identity (annihilates against ⊕-identity A
    pad tiles, min_times-safe); the mask pad is the ⊕-identity so padded
    output columns collapse to zero and slice away cleanly."""
    bm, bk = a.block
    m_pad, k_pad = a.shape
    assert b.shape[0] == k_pad, (b.shape, a.shape)
    n = b.shape[1]
    bn = bm  # square output tiles
    n_pad = -(-n // bn) * bn
    bp = jnp.pad(b.astype(sr.dtype), ((0, 0), (0, n_pad - n)),
                 constant_values=sr.one)
    if mask is None:
        mk = jnp.full((m_pad, n_pad), sr.one, sr.dtype)
        mk = mk.at[:, n:].set(sr.zero)
    else:
        assert mask.shape == (m_pad, n), (mask.shape, (m_pad, n))
        mk = jnp.pad(mask.astype(sr.dtype), ((0, 0), (0, n_pad - n)),
                     constant_values=sr.zero)
    mb, nb = m_pad // bm, n_pad // bn
    tile_any = jnp.any(
        mk.reshape(mb, bm, nb, bn) != sr.zero, axis=(1, 3)).astype(jnp.int32)
    meta = jnp.concatenate([a.tile_cols, tile_any], axis=1)
    return bp, mk, meta, bn, n


def semiring_spgemm(a: PaddedBSR, b: Array, sr: Semiring,
                    mask: Array | None = None,
                    interpret: bool | None = None) -> Array:
    """C = (A ⊕.⊗ B) ⊙ mask. A in ELL-of-tiles; B dense [a.shape[1], N];
    mask dense [a.shape[0], N] or None. Output [a.shape[0], N]."""
    itp = interpret_mode(interpret)
    bp, mk, meta, bn, n = _spgemm_operands(a, b, sr, mask)
    c = semiring_spgemm_padded(a.tiles, meta, bp, mk, sr=sr, bn=bn,
                               interpret=itp)
    return c[:, :n]


def semiring_spgemm_ref(a: PaddedBSR, b: Array, sr: Semiring,
                        mask: Array | None = None) -> Array:
    bp, mk, meta, bn, n = _spgemm_operands(a, b, sr, mask)
    return ref.spgemm_padded_ref(a.tiles, a.tile_cols, bp, mk, sr)[:, :n]


def moe_dispatch_gather(x: Array, slot_tok: Array, block_d: int = 128,
                        interpret: bool | None = None) -> Array:
    """Expert-buffer row gather (tile-SpMSpV analogue; DESIGN.md §5):
    out[s] = x[slot_tok[s]], zero rows for padded slots."""
    from repro.kernels.moe_dispatch import moe_dispatch_gather as _k
    itp = interpret_mode(interpret)
    return _k(x, slot_tok, block_d=block_d, interpret=itp)


def moe_dispatch_gather_ref(x: Array, slot_tok: Array) -> Array:
    return ref.moe_dispatch_gather_ref(x, slot_tok)


def semiring_spmv_ref(a: PaddedBSR, x: Array, sr: Semiring) -> Array:
    return ref.spmv_padded_ref(a.tiles, a.tile_cols, x.astype(sr.dtype), sr)


def semiring_spmspv_ref(a: PaddedBSR, f: Frontier, sr: Semiring) -> Array:
    meta = _spmspv_meta(a, f, sr)
    x_dense = f.to_dense(sr)
    pad = a.shape[1] - x_dense.shape[0]
    if pad:
        x_dense = jnp.pad(x_dense, (0, pad), constant_values=sr.zero)
    return ref.spmspv_padded_ref(a.tiles, meta, x_dense, sr)

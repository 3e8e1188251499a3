"""Static-shape compressed sparse matrix containers (paper §2.1, §4.1).

The paper's design space covers COO / CSR / CSC element formats plus the
tile-granular adaptation we make for TPUs (BSR with dense tiles, §DESIGN.md).
All containers carry **static shapes** (padded to nnz_max / tile budget) so
they are jit/pjit/scan friendly: JAX cannot trace data-dependent shapes.

Padding conventions
-------------------
* COO/CSR/CSC pad ``rows``/``cols`` with an out-of-range index (= M or N) and
  ``vals`` with the semiring zero; XLA scatter drops out-of-range updates, so
  padded entries are no-ops in every segment reduction. CSR's padding sits
  past the last row's end, so its row-sorted reduction never reads it.
* BSR pads the tile list with all-zero tiles pointing at tile-column 0, which
  are ⊕-identity contributions for every supported semiring (zero ⊗ x = zero,
  y ⊕ zero = y) — except min_plus where the pad tile value is +inf.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.semiring import Semiring

Array = jax.Array


def _pad_to(x: np.ndarray, n: int, fill) -> np.ndarray:
    out = np.full((n,) + x.shape[1:], fill, dtype=x.dtype)
    out[: x.shape[0]] = x
    return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class COOMatrix:
    """Coordinate-list format. ``rows``/``cols`` int32 [nnz_max], ``vals`` [nnz_max].

    Entries are stored row-major sorted (so this doubles as CSR's expanded
    segment-id view); padding uses row=shape[0] (out of range → dropped).
    """

    rows: Array
    cols: Array
    vals: Array
    nnz: Array  # scalar int32, true nnz
    shape: Tuple[int, int]

    def tree_flatten(self):
        return (self.rows, self.cols, self.vals, self.nnz), (self.shape,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        rows, cols, vals, nnz = children
        return cls(rows, cols, vals, nnz, aux[0])

    @property
    def nnz_max(self) -> int:
        return self.rows.shape[0]

    def to_dense(self, sr: Semiring) -> Array:
        m, n = self.shape
        dense = jnp.full((m, n), sr.zero, dtype=sr.dtype)
        ok = self.rows < m
        safe_r = jnp.where(ok, self.rows, 0)
        safe_c = jnp.where(ok, self.cols, 0)
        v = jnp.where(ok, self.vals.astype(sr.dtype), sr.zero)
        # ⊕-scatter; for idempotent ⊕ (min/max/or) duplicate coordinates are fine.
        if sr.collective == "psum":
            return dense.at[safe_r, safe_c].add(jnp.where(ok, v, 0))
        if sr.collective == "pmin":
            return dense.at[safe_r, safe_c].min(v)
        return dense.at[safe_r, safe_c].max(v)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSRMatrix:
    """Compressed sparse row: row_ptr [M+1], cols/vals [nnz_max] + expanded
    row segment ids (precomputed so kernels avoid searchsorted at step time).

    ``max_row_nnz`` (static) bounds any single row's length; it sets the
    step count of the row-sorted ⊕-reduction (``scan_steps``).
    """

    row_ptr: Array
    cols: Array
    vals: Array
    seg_ids: Array  # [nnz_max] row index per entry, padded with M
    nnz: Array
    shape: Tuple[int, int]
    max_row_nnz: int

    def tree_flatten(self):
        return ((self.row_ptr, self.cols, self.vals, self.seg_ids, self.nnz),
                (self.shape, self.max_row_nnz))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def nnz_max(self) -> int:
        return self.cols.shape[0]

    @property
    def scan_steps(self) -> int:
        """Doubling steps of ``Semiring.segment_reduce_sorted``: ceil(log2)
        of the longest row, at least 8, so every matrix whose rows hold at
        most 256 entries compiles to the same program."""
        return max(8, (self.max_row_nnz - 1).bit_length())

    def reduce_rows(self, data: Array, sr: Semiring) -> Array:
        """⊕-reduce per-entry ``data`` [B, nnz_max] into rows: [B, M].

        Where ⊕ gives the same bits in any association
        (``sr.exact_in_any_order``), the row-sorted entries take the
        segmented scan. A float sum keeps the scatter, which adds each row
        in entry order as the CSC and COO reductions do, so every path of
        a ⟨+,×⟩ traversal rounds alike."""
        if sr.exact_in_any_order:
            return sr.segment_reduce_sorted(data, self.seg_ids, self.row_ptr,
                                            self.scan_steps)
        return sr.segment_reduce(data.T, self.seg_ids, self.shape[0]).T


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CSCMatrix:
    """Compressed sparse column: col_ptr [N+1], rows/vals sorted by column.

    ``max_col_nnz`` (static) bounds any single column's length — SpMSpV's
    gather-active-columns path materializes (f_max, max_col_nnz) slabs.
    """

    col_ptr: Array
    rows: Array
    vals: Array
    nnz: Array
    shape: Tuple[int, int]
    max_col_nnz: int

    def tree_flatten(self):
        return (self.col_ptr, self.rows, self.vals, self.nnz), (self.shape, self.max_col_nnz)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux[0], aux[1])

    @property
    def nnz_max(self) -> int:
        return self.rows.shape[0]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BSRMatrix:
    """Block-sparse row format with **dense (bm, bn) tiles** — the TPU-native
    adaptation of CSC/CSR (DESIGN.md §2): tile metadata is CSR-of-tiles.

    tiles:        [t_max, bm, bn]  dense tile payloads (semiring dtype)
    tile_cols:    [t_max] int32    tile-column index per tile (pad: 0 w/ zero tile)
    tile_row_ptr: [n_block_rows+1] int32
    """

    tiles: Array
    tile_cols: Array
    tile_row_ptr: Array
    shape: Tuple[int, int]
    block: Tuple[int, int]

    def tree_flatten(self):
        return (self.tiles, self.tile_cols, self.tile_row_ptr), (self.shape, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux[0], aux[1])

    @property
    def n_block_rows(self) -> int:
        return self.tile_row_ptr.shape[0] - 1

    @property
    def t_max(self) -> int:
        return self.tiles.shape[0]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PaddedBSR:
    """ELL-of-tiles: every block row padded to T slots — the layout the
    Pallas kernels consume (uniform grid, scalar-prefetched column indices).

    tiles:     [mb, T, bm, bn]  pad slots hold the ⊕-identity tile
    tile_cols: [mb, T] int32    pad slots point at tile-column 0
    """

    tiles: Array
    tile_cols: Array
    shape: Tuple[int, int]
    block: Tuple[int, int]

    def tree_flatten(self):
        return (self.tiles, self.tile_cols), (self.shape, self.block)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux[0], aux[1])

    @property
    def n_block_rows(self) -> int:
        return self.tiles.shape[0]

    @property
    def slots(self) -> int:
        return self.tiles.shape[1]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SlicedELL:
    """sell-C-σ of tiles: block rows sorted by tile count inside σ-row
    windows, grouped into slices of C rows, each slice padded only to *its
    own* max slot count (vs the global max of :class:`PaddedBSR`).  On
    hub-skewed rmat graphs this collapses the pad volume the few hub rows
    force onto every other row.

    tiles:     [slot_total, bm, bn]  flat slice-major payloads; pad slots
               hold the ⊕-identity tile (same convention as PaddedBSR)
    tile_cols: [slot_total] int32    pad slots point at tile-column 0
    row_meta:  [mb, 3] int32 in compute (permuted) order:
               (out_block, base, n_real) — program i streams
               tiles[base : base + n_real] and ⊕-scatters into output
               block ``out_block`` (the Retrieve-side permutation).
    """

    tiles: Array
    tile_cols: Array
    row_meta: Array
    shape: Tuple[int, int]
    block: Tuple[int, int]
    slice_height: int
    sigma: int

    def tree_flatten(self):
        return (self.tiles, self.tile_cols, self.row_meta), (
            self.shape, self.block, self.slice_height, self.sigma)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def n_block_rows(self) -> int:
        return self.row_meta.shape[0]

    @property
    def slot_total(self) -> int:
        return self.tiles.shape[0]

    @property
    def real_slots(self) -> int:
        return int(np.asarray(self.row_meta[:, 2]).sum())

    def to_dense(self, sr: Semiring) -> Array:
        """Round-trip helper (tests): ⊕-scatter every real tile back into a
        dense [mb·bm, nb·bn] array in the original (unpermuted) row order."""
        bm, bn = self.block
        m, n = self.shape
        dense = np.full((m, n), sr.zero, dtype=np.dtype(sr.dtype))
        meta = np.asarray(self.row_meta)
        tiles = np.asarray(self.tiles)
        cols = np.asarray(self.tile_cols)
        for out_block, base, n_real in meta:
            r0 = int(out_block) * bm
            for j in range(int(n_real)):
                c0 = int(cols[base + j]) * bn
                blk = dense[r0:r0 + bm, c0:c0 + bn]
                if sr.collective == "pmin":
                    np.minimum(blk, tiles[base + j], out=blk)
                elif sr.collective == "psum":
                    np.add(blk, tiles[base + j], out=blk)
                else:
                    np.maximum(blk, tiles[base + j], out=blk)
        return jnp.asarray(dense)


# ---------------------------------------------------------------------------
# Builders (host-side, numpy; run once per dataset, amortized like the paper's
# matrix-load phase which §4.1 excludes from timing).
# ---------------------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def build_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
              shape: Tuple[int, int], sr: Semiring, nnz_max: int | None = None) -> COOMatrix:
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    nnz = rows.shape[0]
    nnz_max = nnz_max or _round_up(max(nnz, 1), 8)
    zero = np.inf if sr.collective == "pmin" else 0
    return COOMatrix(
        rows=jnp.asarray(_pad_to(rows.astype(np.int32), nnz_max, shape[0])),
        cols=jnp.asarray(_pad_to(cols.astype(np.int32), nnz_max, shape[1])),
        vals=jnp.asarray(_pad_to(vals.astype(np.dtype(sr.dtype)), nnz_max, zero)),
        nnz=jnp.asarray(nnz, jnp.int32),
        shape=shape,
    )


def build_csr(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
              shape: Tuple[int, int], sr: Semiring, nnz_max: int | None = None) -> CSRMatrix:
    coo = build_coo(rows, cols, vals, shape, sr, nnz_max)
    m = shape[0]
    counts = np.bincount(np.asarray(coo.rows)[: int(coo.nnz)], minlength=m + 1)[:m]
    row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return CSRMatrix(
        row_ptr=jnp.asarray(row_ptr),
        cols=coo.cols,
        vals=coo.vals,
        seg_ids=coo.rows,
        nnz=coo.nnz,
        shape=shape,
        max_row_nnz=int(counts.max(initial=1)),
    )


def build_csc(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
              shape: Tuple[int, int], sr: Semiring, nnz_max: int | None = None) -> CSCMatrix:
    order = np.lexsort((rows, cols))
    rows, cols, vals = rows[order], cols[order], vals[order]
    nnz = rows.shape[0]
    nnz_max = nnz_max or _round_up(max(nnz, 1), 8)
    n = shape[1]
    counts = np.bincount(cols, minlength=n)
    col_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    zero = np.inf if sr.collective == "pmin" else 0
    max_col_nnz = int(counts.max()) if nnz else 1
    return CSCMatrix(
        col_ptr=jnp.asarray(col_ptr),
        rows=jnp.asarray(_pad_to(rows.astype(np.int32), nnz_max, shape[0])),
        vals=jnp.asarray(_pad_to(vals.astype(np.dtype(sr.dtype)), nnz_max, zero)),
        nnz=jnp.asarray(nnz, jnp.int32),
        shape=shape,
        max_col_nnz=max(1, max_col_nnz),
    )


def build_bsr(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
              shape: Tuple[int, int], sr: Semiring,
              block: Tuple[int, int] = (128, 128),
              t_max: int | None = None) -> BSRMatrix:
    """Densify nonzero (bm, bn) tiles; CSR-of-tiles metadata.

    For min_plus the dense-tile background is +inf (⊗-annihilator under min,+
    would be wrong: inf + x = inf, min-identity ✓).
    """
    bm, bn = block
    m, n = shape
    mb, nb = -(-m // bm), -(-n // bn)
    trow, tcol = rows // bm, cols // bn
    tile_id = trow * nb + tcol
    order = np.argsort(tile_id, kind="stable")
    rows, cols, vals, tile_id = rows[order], cols[order], vals[order], tile_id[order]
    uniq, starts = np.unique(tile_id, return_index=True)
    n_tiles = uniq.shape[0]
    t_max = t_max or max(1, int(n_tiles))
    background = np.inf if sr.collective == "pmin" else 0
    np_dtype = np.dtype(sr.dtype)
    tiles = np.full((t_max, bm, bn), background, dtype=np_dtype)
    tile_cols_np = np.zeros((t_max,), dtype=np.int32)
    ends = np.append(starts[1:], rows.shape[0])
    tile_counts = np.zeros((mb,), dtype=np.int64)
    for k in range(n_tiles):
        s, e = starts[k], ends[k]
        tr, tc = int(uniq[k]) // nb, int(uniq[k]) % nb
        lr = rows[s:e] - tr * bm
        lc = cols[s:e] - tc * bn
        if sr.collective == "pmin":
            np.minimum.at(tiles[k], (lr, lc), vals[s:e].astype(np_dtype))
        elif sr.collective == "psum":
            np.add.at(tiles[k], (lr, lc), vals[s:e].astype(np_dtype))
        else:
            np.maximum.at(tiles[k], (lr, lc), vals[s:e].astype(np_dtype))
        tile_cols_np[k] = tc
        tile_counts[tr] += 1
    tile_row_ptr = np.concatenate([[0], np.cumsum(tile_counts)]).astype(np.int32)
    return BSRMatrix(
        tiles=jnp.asarray(tiles),
        tile_cols=jnp.asarray(tile_cols_np),
        tile_row_ptr=jnp.asarray(tile_row_ptr),
        shape=(mb * bm, nb * bn),
        block=block,
    )


def _densify_tiles(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                   shape: Tuple[int, int], sr: Semiring,
                   block: Tuple[int, int]) -> list[dict[int, np.ndarray]]:
    """Shared tile-densification pass: per block row, a {tile_col: dense
    (bm, bn) tile} dict (tile background = ⊕-identity).  Both ELL-of-tiles
    (:func:`build_bsr_padded`) and sliced-ELL (:func:`build_sell`) builders
    consume this, so a (PaddedBSR, SlicedELL) pair built from the same edge
    list holds bit-identical tile payloads in the same per-row order."""
    bm, bn = block
    m, n = shape
    mb, nb = -(-m // bm), -(-n // bn)
    trow, tcol = rows // bm, cols // bn
    background = np.inf if sr.collective == "pmin" else 0
    np_dtype = np.dtype(sr.dtype)

    per_row_tiles: list[dict[int, np.ndarray]] = [dict() for _ in range(mb)]
    order = np.lexsort((tcol, trow))
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    trow_s, tcol_s = trow[order], tcol[order]
    keys = trow_s.astype(np.int64) * nb + tcol_s
    uniq, starts = np.unique(keys, return_index=True)
    ends = np.append(starts[1:], keys.shape[0])
    for k in range(uniq.shape[0]):
        s, e = starts[k], ends[k]
        tr, tc = int(uniq[k]) // nb, int(uniq[k]) % nb
        tile = np.full((bm, bn), background, dtype=np_dtype)
        lr = rows_s[s:e] - tr * bm
        lc = cols_s[s:e] - tc * bn
        if sr.collective == "pmin":
            np.minimum.at(tile, (lr, lc), vals_s[s:e].astype(np_dtype))
        elif sr.collective == "psum":
            np.add.at(tile, (lr, lc), vals_s[s:e].astype(np_dtype))
        else:
            np.maximum.at(tile, (lr, lc), vals_s[s:e].astype(np_dtype))
        per_row_tiles[tr][tc] = tile
    return per_row_tiles


def build_bsr_padded(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                     shape: Tuple[int, int], sr: Semiring,
                     block: Tuple[int, int] = (128, 128),
                     slots: int | None = None) -> PaddedBSR:
    """ELL-of-tiles builder: densify nonzero tiles, pad each block row to a
    uniform slot count (static Pallas grid)."""
    bm, bn = block
    m, n = shape
    mb, nb = -(-m // bm), -(-n // bn)
    background = np.inf if sr.collective == "pmin" else 0
    np_dtype = np.dtype(sr.dtype)
    per_row_tiles = _densify_tiles(rows, cols, vals, shape, sr, block)

    t_needed = max(1, max((len(d) for d in per_row_tiles), default=1))
    slots = slots or t_needed
    assert slots >= t_needed, f"slots={slots} < needed {t_needed}"
    tiles = np.full((mb, slots, bm, bn), background, dtype=np_dtype)
    tile_cols_np = np.zeros((mb, slots), dtype=np.int32)
    for i, d in enumerate(per_row_tiles):
        for j, (tc, tile) in enumerate(sorted(d.items())):
            tiles[i, j] = tile
            tile_cols_np[i, j] = tc
    return PaddedBSR(
        tiles=jnp.asarray(tiles),
        tile_cols=jnp.asarray(tile_cols_np),
        shape=(mb * bm, nb * bn),
        block=block,
    )


def build_sell(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
               shape: Tuple[int, int], sr: Semiring,
               block: Tuple[int, int] = (128, 128),
               c: int = 8, sigma: int | None = None) -> SlicedELL:
    """sell-C-σ builder: densify tiles (same pass as :func:`build_bsr_padded`),
    sort block rows by descending tile count within σ-row windows, group into
    slices of ``c`` rows, pad each slice to its own max slot count.

    ``sigma=None`` sorts globally (σ = mb).  Per-row tile order is tile-col
    sorted — identical to the PaddedBSR slot order, so a fused kernel that
    streams ``tiles[base : base + n_real]`` reduces in exactly the order the
    ELL kernel does (bit-identity across formats for every semiring).
    """
    bm, bn = block
    m, n = shape
    mb, nb = -(-m // bm), -(-n // bn)
    background = np.inf if sr.collective == "pmin" else 0
    np_dtype = np.dtype(sr.dtype)
    per_row_tiles = _densify_tiles(rows, cols, vals, shape, sr, block)
    counts = np.array([len(d) for d in per_row_tiles], dtype=np.int64)

    sigma = sigma or mb
    if sigma < c:
        raise ValueError(f"sigma={sigma} must be >= slice height c={c}")
    perm: list[int] = []
    for w0 in range(0, mb, sigma):
        w1 = min(w0 + sigma, mb)
        local = np.argsort(-counts[w0:w1], kind="stable") + w0
        perm.extend(int(i) for i in local)
    perm_np = np.asarray(perm, dtype=np.int64)

    # Per-slice width = that slice's max tile count (>=1 so every row owns at
    # least one slot and the flat layout never aliases across rows).
    bases = np.zeros((mb,), dtype=np.int64)
    slot_total = 0
    for s0 in range(0, mb, c):
        s1 = min(s0 + c, mb)
        width = max(1, int(counts[perm_np[s0:s1]].max()))
        for i in range(s0, s1):
            bases[i] = slot_total + (i - s0) * width
        slot_total += (s1 - s0) * width

    tiles = np.full((max(1, slot_total), bm, bn), background, dtype=np_dtype)
    tile_cols_np = np.zeros((max(1, slot_total),), dtype=np.int32)
    row_meta = np.zeros((mb, 3), dtype=np.int32)
    for i, r in enumerate(perm_np):
        d = per_row_tiles[int(r)]
        base = int(bases[i])
        row_meta[i] = (int(r), base, len(d))
        for j, (tc, tile) in enumerate(sorted(d.items())):
            tiles[base + j] = tile
            tile_cols_np[base + j] = tc
    return SlicedELL(
        tiles=jnp.asarray(tiles),
        tile_cols=jnp.asarray(tile_cols_np),
        row_meta=jnp.asarray(row_meta),
        shape=(mb * bm, nb * bn),
        block=block,
        slice_height=c,
        sigma=sigma,
    )


def sell_stream_cost(counts: np.ndarray, block: Tuple[int, int],
                     c: int, sigma: int, elem_bytes: int = 4) -> dict:
    """Deterministic bytes model for one sell-C-σ candidate, computed from
    per-block-row tile counts alone (no tiles materialized).  The fused
    kernel streams only real slots plus one x-block gather per real slot;
    pad slots cost storage (and Load-phase shard bytes) but are never
    DMA'd, so they enter with a discounted weight."""
    bm, bn = block
    mb = counts.shape[0]
    sigma = sigma or mb
    perm: list[np.ndarray] = []
    for w0 in range(0, mb, sigma):
        w1 = min(w0 + sigma, mb)
        perm.append(np.sort(counts[w0:w1])[::-1])
    sorted_counts = np.concatenate(perm) if perm else np.zeros((0,), np.int64)
    slot_total = 0
    for s0 in range(0, mb, c):
        s1 = min(s0 + c, mb)
        slot_total += (s1 - s0) * max(1, int(sorted_counts[s0:s1].max()))
    real = int(counts.sum())
    tile_bytes = bm * bn * elem_bytes
    streamed = real * (tile_bytes + bn * elem_bytes) + mb * bm * elem_bytes
    stored = slot_total * tile_bytes
    return {
        "slot_total": int(slot_total),
        "real_slots": real,
        "streamed_bytes": int(streamed),
        "stored_bytes": int(stored),
        # streamed dominates; storage/Load padding enters at 1/8 weight
        "cost": int(streamed + stored // 8),
    }


def autotune_sell(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                  shape: Tuple[int, int], sr: Semiring,
                  blocks: tuple = ((8, 8), (16, 16), (32, 32)),
                  cs: tuple = (4, 8), sigmas: tuple = (None, 32),
                  elem_bytes: int = 4):
    """Static autotuner: sweep (block, C, σ) candidates, score each with the
    deterministic :func:`sell_stream_cost` bytes model, build only the
    winner.  Returns ``(SlicedELL, report)`` where ``report`` is the scored
    candidate list (best first) for logging/benchmark tables."""
    report = []
    for block in blocks:
        bm, _ = block
        m, _ = shape
        mb = -(-m // bm)
        trow, tcol = rows // block[0], cols // block[1]
        keys = np.unique(trow.astype(np.int64) * (-(-shape[1] // block[1])) + tcol)
        counts = np.bincount((keys // (-(-shape[1] // block[1]))).astype(np.int64),
                             minlength=mb)
        for c in cs:
            for sigma in sigmas:
                sig = sigma or mb
                if sig < c:
                    continue
                stats = sell_stream_cost(counts, block, c, sig, elem_bytes)
                report.append({"block": block, "c": c, "sigma": sig, **stats})
    report.sort(key=lambda r: (r["cost"], r["block"], r["c"], r["sigma"]))
    best = report[0]
    sell = build_sell(rows, cols, vals, shape, sr, block=best["block"],
                      c=best["c"], sigma=best["sigma"])
    return sell, report


def coo_from_dense(dense: np.ndarray, sr: Semiring):
    """Test helper: extract structural nonzeros (≠ semiring zero)."""
    zero = np.inf if sr.collective == "pmin" else 0
    rows, cols = np.nonzero(dense != zero)
    return rows.astype(np.int32), cols.astype(np.int32), dense[rows, cols]

"""The row-sorted CSR ⊕-reduction (``Semiring.segment_reduce_sorted``,
which ``CSRMatrix.reduce_rows`` takes wherever ⊕ is exact in any order)
against the scatter it replaces, ``Semiring.segment_reduce``
(jax.ops.segment_{max,min,sum})."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_csr
from repro.core.semiring import (
    BOOL_OR_AND, MIN_PLUS, MIN_TIMES, PLUS_AND, PLUS_TIMES,
)

M = 40                 # rows
HUB, HUB_LEN = 7, 300  # one row longer than 2^8 entries: 9 doubling steps
SINGLE = 11            # a row with one entry
PAD = 29               # nnz_max - nnz: padding entries past the last row


def _graph():
    """Row-sorted entries with empty rows (every third row, and the last
    rows), a hub row of HUB_LEN entries and a single-entry row."""
    rng = np.random.default_rng(3)
    rows, cols = [], []
    for r in range(M - 4):
        if r % 3 == 0 and r != HUB:
            continue
        k = HUB_LEN if r == HUB else 1 if r == SINGLE else int(rng.integers(2, 30))
        rows.append(np.full(k, r))
        cols.append(rng.integers(0, M, k))
    return np.concatenate(rows), np.concatenate(cols)


def _data(sr, shape, rng):
    if sr in (BOOL_OR_AND, PLUS_AND):
        return rng.integers(0, 2, shape).astype(np.int32)
    v = (rng.random(shape) * 10 + 0.5).astype(np.float32)
    if sr.zero == jnp.inf:  # some products are +inf (unreached), as in SSSP
        v = np.where(rng.random(shape) < 0.3, np.inf, v).astype(np.float32)
    return v


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("sr", [BOOL_OR_AND, MIN_PLUS, MIN_TIMES, PLUS_TIMES,
                                PLUS_AND], ids=lambda s: s.name)
def test_segment_reduce_sorted_equals_scatter(sr, b):
    rows, cols = _graph()
    rng = np.random.default_rng(b)
    a = build_csr(rows, cols, _data(sr, rows.size, rng), (M, M), sr,
                  nnz_max=rows.size + PAD)
    assert a.max_row_nnz == HUB_LEN and a.scan_steps == 9
    data = jnp.asarray(_data(sr, (b, a.nnz_max), rng))
    # padding entries carry ⊕-zero, as every CSR matvec masks them
    data = jnp.where(a.seg_ids[None] < M, data, sr.zero)

    got = np.asarray(sr.segment_reduce_sorted(data, a.seg_ids, a.row_ptr,
                                              a.scan_steps))
    want = np.asarray(sr.segment_reduce(data.T, a.seg_ids, M).T)
    # CSR matvecs take the scan wherever it is exact; a float sum keeps
    # the scatter, so every ⟨+,×⟩ path adds a row in entry order
    np.testing.assert_array_equal(
        np.asarray(a.reduce_rows(data, sr)),
        got if sr.exact_in_any_order else want)
    assert got.shape == (b, M)
    counts = np.bincount(rows, minlength=M)
    assert (counts == 0).any() and counts[SINGLE] == 1
    np.testing.assert_array_equal(got[:, counts == 0], sr.zero)
    if sr is PLUS_TIMES:  # a float sum is only associated differently
        np.testing.assert_allclose(got, want, rtol=1e-5)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("longest,steps", [(1, 8), (2, 8), (256, 8),
                                           (257, 9), (25000, 15)])
def test_scan_steps_cover_the_longest_row(longest, steps):
    """ceil(log2) of the longest row, floored at 8: every graph whose rows
    hold at most 256 entries compiles to one program."""
    rows = np.concatenate([np.zeros(longest, np.int64), [1]])
    cols = np.concatenate([np.arange(longest) % 64, [0]])
    a = build_csr(rows, cols, np.ones(rows.size, np.int32), (2, 64),
                  BOOL_OR_AND)
    assert a.max_row_nnz == longest
    assert a.scan_steps == steps

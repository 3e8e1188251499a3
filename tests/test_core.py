"""Core semiring sparse engine: formats × semirings vs the dense oracle,
plus algebraic property tests (hypothesis)."""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")

from repro.core import (
    BOOL_OR_AND, MIN_PLUS, PLUS_TIMES,
    build_coo, build_csc, build_csr, build_bsr,
    frontier_from_dense, spmspv, spmv, spmv_bsr_ref,
)

SEMIRINGS = [PLUS_TIMES, MIN_PLUS, BOOL_OR_AND]


def make_problem(sr, n, density, vec_density, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((n, n)) < density
    if sr.name == "min_plus":
        dense = np.where(mask, rng.integers(1, 9, (n, n)).astype(np.float32), np.inf)
        x = np.where(rng.random(n) < vec_density, rng.random(n).astype(np.float32), np.inf)
    elif sr.name == "bool_or_and":
        dense = mask.astype(np.int32)
        x = (rng.random(n) < vec_density).astype(np.int32)
    else:
        dense = np.where(mask, rng.random((n, n)).astype(np.float32), 0.0)
        x = np.where(rng.random(n) < vec_density, rng.random(n).astype(np.float32), 0.0)
    rows, cols = np.nonzero(mask)
    vals = dense[rows, cols]
    oracle = np.asarray(sr.matvec(jnp.asarray(dense, sr.dtype), jnp.asarray(x, sr.dtype)))
    return rows, cols, vals.astype(np.dtype(sr.dtype)), x.astype(np.dtype(sr.dtype)), oracle


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("n,density", [(32, 0.2), (100, 0.05), (257, 0.02)])
def test_spmv_formats_match_oracle(sr, n, density):
    rows, cols, vals, x, oracle = make_problem(sr, n, density, 0.3, seed=n)
    xj = jnp.asarray(x, sr.dtype)
    coo = build_coo(rows, cols, vals, (n, n), sr)
    csr = build_csr(rows, cols, vals, (n, n), sr)
    assert csr.max_row_nnz == max(1, np.bincount(rows, minlength=n).max())
    np.testing.assert_allclose(np.asarray(spmv(coo, xj, sr)), oracle, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(spmv(csr, xj, sr)), oracle, rtol=1e-5)
    bsr = build_bsr(rows, cols, vals, (n, n), sr, block=(16, 16))
    xp = jnp.pad(xj, (0, bsr.shape[1] - n), constant_values=sr.zero)
    np.testing.assert_allclose(np.asarray(spmv_bsr_ref(bsr, xp, sr))[:n], oracle, rtol=1e-5)


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("vec_density", [0.01, 0.1, 0.5, 1.0])
def test_spmspv_formats_match_oracle(sr, vec_density):
    n = 128
    rows, cols, vals, x, oracle = make_problem(sr, n, 0.05, vec_density, seed=7)
    xj = jnp.asarray(x, sr.dtype)
    f = frontier_from_dense(xj, sr)
    csr = build_csr(rows, cols, vals, (n, n), sr)
    csc = build_csc(rows, cols, vals, (n, n), sr)
    np.testing.assert_allclose(np.asarray(spmspv(csr, f, sr)), oracle, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(spmspv(csc, f, sr)), oracle, rtol=1e-5)


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
def test_frontier_roundtrip(sr):
    _, _, _, x, _ = make_problem(sr, 64, 0.1, 0.3, seed=3)
    xj = jnp.asarray(x, sr.dtype)
    f = frontier_from_dense(xj, sr)
    np.testing.assert_array_equal(np.asarray(f.to_dense(sr)), np.asarray(xj))
    assert int(f.count) == int(np.sum(x != (np.inf if sr.name == "min_plus" else 0)))


# ----------------------------- property tests -----------------------------

@hypothesis.given(
    st.integers(1, 40), st.integers(0, 2**31 - 1),
    st.sampled_from(["plus_times", "min_plus", "bool_or_and"]),
)
@hypothesis.settings(max_examples=30, deadline=None)
def test_property_spmv_linear_over_semiring(n, seed, sr_name):
    """y(A, x) must equal the dense semiring matvec for random instances."""
    sr = {s.name: s for s in SEMIRINGS}[sr_name]
    rows, cols, vals, x, oracle = make_problem(sr, n, 0.3, 0.5, seed=seed % 10000)
    if rows.size == 0:
        return
    coo = build_coo(rows, cols, vals, (n, n), sr)
    y = np.asarray(spmv(coo, jnp.asarray(x, sr.dtype), sr))
    np.testing.assert_allclose(y, oracle, rtol=1e-4)


@hypothesis.given(st.integers(2, 30), st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=25, deadline=None)
def test_property_spmspv_equals_spmv_on_densified(n, seed):
    """Invariant: SpMSpV(frontier(x)) == SpMV(x) for every semiring."""
    for sr in SEMIRINGS:
        rows, cols, vals, x, _ = make_problem(sr, n, 0.3, 0.4, seed=seed % 9999)
        if rows.size == 0:
            continue
        csr = build_csr(rows, cols, vals, (n, n), sr)
        csc = build_csc(rows, cols, vals, (n, n), sr)
        xj = jnp.asarray(x, sr.dtype)
        f = frontier_from_dense(xj, sr)
        y_spmv = np.asarray(spmv(csr, xj, sr))
        np.testing.assert_allclose(np.asarray(spmspv(csr, f, sr)), y_spmv, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(spmspv(csc, f, sr)), y_spmv, rtol=1e-4)


@hypothesis.given(st.integers(1, 25), st.integers(0, 2**31 - 1))
@hypothesis.settings(max_examples=20, deadline=None)
def test_property_semiring_identities(n, seed):
    """⊕-identity (zero vector in, zero out for ⊗-annihilator) and
    ⊗-identity (identity matrix in ⟨⊕,⊗⟩ behaves as identity map)."""
    for sr in SEMIRINGS:
        rng = np.random.default_rng(seed % 99991)
        if sr.name == "bool_or_and":
            x = (rng.random(n) < 0.5).astype(np.int32)
        elif sr.name == "min_plus":
            x = np.where(rng.random(n) < 0.5, rng.random(n).astype(np.float32), np.inf)
        else:
            x = rng.random(n).astype(np.float32)
        eye_r = np.arange(n, dtype=np.int32)
        vals = np.full(n, sr.one, dtype=np.dtype(sr.dtype))
        ident = build_coo(eye_r, eye_r, vals, (n, n), sr)
        y = np.asarray(spmv(ident, jnp.asarray(x, sr.dtype), sr))
        np.testing.assert_allclose(y, x.astype(np.dtype(sr.dtype)), rtol=1e-6)

"""Masked semiring SpGEMM: every execution path (element, dense-blocked,
BSR oracle, Pallas tile kernel) vs the dense oracle across all exported
semirings, plus the distributed row/col/2d merge strategies."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BOOL_OR_AND, MIN_PLUS, MIN_TIMES, PLUS_AND, PLUS_TIMES,
    build_bsr_padded, build_coo, build_csr, spgemm_blocked, spgemm_dense_ref,
    spgemm_masked,
)

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
SEMIRINGS = [PLUS_TIMES, MIN_PLUS, BOOL_OR_AND, PLUS_AND, MIN_TIMES]


def make_problem(sr, n, k, m, density, seed, masked=True):
    """(a_dense, b_dense, mask, edge list) in the semiring's safe domain
    (min_times operands stay strictly positive, see semiring.py)."""
    rng = np.random.default_rng(seed)
    mask_a = rng.random((n, k)) < density
    mask_m = rng.random((n, m)) < 0.4
    if sr.collective == "pmin":
        a = np.where(mask_a, rng.integers(1, 9, (n, k)).astype(np.float32),
                     np.inf)
        b = rng.integers(1, 9, (k, m)).astype(np.float32)
        mask = np.where(mask_m, 1.0, np.inf).astype(np.float32)
    elif sr.dtype == jnp.int32:
        a = mask_a.astype(np.int32)
        b = (rng.random((k, m)) < 0.4).astype(np.int32)
        mask = mask_m.astype(np.int32)
    else:
        a = np.where(mask_a, rng.random((n, k)).astype(np.float32), 0.0)
        b = rng.random((k, m)).astype(np.float32)
        mask = mask_m.astype(np.float32)
    if not masked:
        mask = None
    rows, cols = np.nonzero(mask_a)
    vals = a[rows, cols].astype(np.dtype(sr.dtype))
    return a, b, mask, (rows.astype(np.int32), cols.astype(np.int32), vals)


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("masked", [True, False], ids=["masked", "unmasked"])
def test_spgemm_paths_match_oracle(sr, masked):
    n, k, m = 37, 52, 29
    a, b, mask, (rows, cols, vals) = make_problem(sr, n, k, m, 0.12, seed=7,
                                                  masked=masked)
    aj = jnp.asarray(a, sr.dtype)
    bj = jnp.asarray(b, sr.dtype)
    mj = None if mask is None else jnp.asarray(mask, sr.dtype)
    oracle = np.asarray(spgemm_dense_ref(aj, bj, sr, mj))

    blocked = np.asarray(spgemm_blocked(aj, bj, sr, mj, block_k=16))
    np.testing.assert_allclose(blocked, oracle, rtol=1e-5)

    for build in (build_coo, build_csr):
        sp = build(rows, cols, vals, (n, k), sr)
        got = np.asarray(spgemm_masked(sp, bj, sr, mj))
        np.testing.assert_allclose(got, oracle, rtol=1e-5,
                                   err_msg=f"{build.__name__}/{sr.name}")


@pytest.mark.parametrize("sr", SEMIRINGS, ids=lambda s: s.name)
def test_spgemm_bsr_kernel_matches_oracle(sr):
    """Pallas tile kernel (interpret mode) + its jnp oracle vs ground truth,
    including the block-padding of B/mask inside ops._spgemm_operands."""
    n, k, m = 37, 52, 29
    a, b, mask, (rows, cols, vals) = make_problem(sr, n, k, m, 0.12, seed=3)
    bsr = build_bsr_padded(rows, cols, vals, (n, k), sr, block=(16, 16))
    k_pad, m_pad = bsr.shape[1], bsr.shape[0]
    bp = np.full((k_pad, m), sr.one, dtype=np.dtype(sr.dtype))
    bp[:k] = b
    mp = np.full((m_pad, m),
                 np.inf if sr.collective == "pmin" else 0,
                 dtype=np.dtype(sr.dtype))
    mp[:n] = mask
    oracle = np.asarray(spgemm_dense_ref(
        jnp.asarray(a, sr.dtype), jnp.asarray(b, sr.dtype), sr,
        jnp.asarray(mask, sr.dtype)))
    for impl in ("ref", "auto"):
        got = np.asarray(spgemm_masked(bsr, jnp.asarray(bp, sr.dtype), sr,
                                       jnp.asarray(mp, sr.dtype),
                                       impl=impl))[:n]
        np.testing.assert_allclose(got, oracle, rtol=1e-5,
                                   err_msg=f"bsr/{impl}/{sr.name}")


def test_spgemm_mask_skips_entries():
    """Structural masking: entries outside the mask collapse to the
    ⊕-identity even when the unmasked product is nonzero there."""
    sr = PLUS_TIMES
    a = np.ones((8, 8), np.float32)
    b = np.ones((8, 8), np.float32)
    mask = np.zeros((8, 8), np.float32)
    mask[2, 3] = 1.0
    c = np.array(spgemm_blocked(jnp.asarray(a), jnp.asarray(b), sr,
                                jnp.asarray(mask), block_k=4))
    assert c[2, 3] == 8.0
    c[2, 3] = 0.0
    assert (c == 0).all()


DIST_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.distributed import make_distributed_spgemm
from repro.core.spgemm import spgemm_dense_ref

rng = np.random.default_rng(11)
n, nrhs = 128, 24
dense_np = (rng.random((n, n)) < 0.08).astype(np.float32) * rng.integers(1, 9, (n, n))
rows, cols = np.nonzero(dense_np)
vals = dense_np[rows, cols].astype(np.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("dr", "dc"))

checked = 0
for sr in (PLUS_TIMES, MIN_PLUS, BOOL_OR_AND, PLUS_AND):
    if sr.name == "min_plus":
        dense = np.where(dense_np != 0, dense_np, np.inf).astype(np.float32)
        b = rng.integers(1, 9, (n, nrhs)).astype(np.float32); v = vals; fill = np.inf
        mask = np.where(rng.random((n, nrhs)) < 0.5, 1.0, np.inf).astype(np.float32)
    elif sr.dtype == jnp.int32:
        dense = (dense_np != 0).astype(np.int32)
        b = (rng.random((n, nrhs)) < 0.4).astype(np.int32)
        v = np.ones_like(vals, dtype=np.int32); fill = 0
        mask = (rng.random((n, nrhs)) < 0.5).astype(np.int32)
    else:
        dense = dense_np
        b = rng.random((n, nrhs)).astype(np.float32); v = vals; fill = 0.0
        mask = (rng.random((n, nrhs)) < 0.5).astype(np.float32)
    oracle = np.asarray(spgemm_dense_ref(jnp.asarray(dense, sr.dtype),
                                         jnp.asarray(b, sr.dtype), sr,
                                         jnp.asarray(mask, sr.dtype)))
    for strategy, grid, fmt in [("row", (8, 1), "csr"), ("col", (1, 8), "csr"),
                                ("2d", (2, 4), "coo")]:
        for balance in ("rows", "nnz"):
            pm = partition(rows, cols, v, (n, n), grid, fmt, sr,
                           balance=balance)
            bs = jnp.asarray(pm.plan.shard_input_rows(b, sr.one), sr.dtype)
            ms = jnp.asarray(pm.plan.shard_output_rows(mask, fill), sr.dtype)
            fn = make_distributed_spgemm(mesh, pm, sr, strategy)
            c = np.asarray(jax.jit(fn)(pm.parts, bs, ms))
            cg = pm.plan.unshard_output_rows(c)
            np.testing.assert_allclose(cg[:n], oracle, rtol=1e-5,
                                       err_msg=f"{sr.name}/{strategy}/{fmt}/{balance}")
            checked += 1
print(f"DIST_SPGEMM_OK {checked}")
"""


@pytest.mark.slow
def test_distributed_spgemm_strategies():
    """Masked SpGEMM over every strategy × balance mode: B rows shard via
    the plan's input layout, masks/outputs via the output layout."""
    env = dict(os.environ, PYTHONPATH=REPO_SRC)
    out = subprocess.run([sys.executable, "-c", DIST_WORKER], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DIST_SPGEMM_OK 24" in out.stdout

"""Compile the graph kernels and a served BFS runner for a described TPU v5e.

Interpret mode runs the kernel bodies on the CPU and accepts layouts and
slices the TPU compiler refuses. These tests hand each Pallas kernel (at
the 128x128 tiles the engine builds) and one batched BFS runner to the
TPU compiler for a ``v5e:2x2`` topology that is described, not attached,
and check that each compiles to a Mosaic custom call. Nothing runs, so
results are checked elsewhere (tests/test_kernels.py, chip_smoke.py).

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports every test file.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.formats import CSRMatrix
from repro.core.semiring import (
    BOOL_OR_AND, MIN_PLUS, PLUS_AND, PLUS_TIMES,
)
from repro.core.spmv import spmv_batch
from repro.kernels.semiring_spmv import (
    semiring_spmv_fused_padded, semiring_spmv_padded, semiring_spmv_sell,
)
from repro.kernels.spgemm_tiles import semiring_spgemm_padded
from repro.kernels.spmspv_tiles import (
    semiring_spmspv_fused_padded, semiring_spmspv_padded,
)

MB, T, B = 64, 8, 128          # block rows, slots per row, tile edge
VECTOR_SEMIRINGS = [PLUS_TIMES, MIN_PLUS, BOOL_OR_AND]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"
    return text


def _kernel_args(name, sr, sh):
    dt = sr.dtype
    n = MB * B
    tiles = _arg((MB, T, B, B), dt, sh)
    x = _arg((n,), dt, sh)
    if name == "spmv":
        return semiring_spmv_padded, (tiles, _arg((MB, T), jnp.int32, sh), x)
    if name in ("spmv_fused", "spmv_fused_chunked"):
        return semiring_spmv_fused_padded, (
            tiles, _arg((MB, 1 + T), jnp.int32, sh), x)
    if name == "spmv_sell":
        slots = MB * T // 2
        return semiring_spmv_sell, (
            _arg((slots, B, B), dt, sh), _arg((slots,), jnp.int32, sh),
            _arg((MB, 3), jnp.int32, sh), x)
    meta = _arg((MB, 1 + 2 * T), jnp.int32, sh)
    if name == "spmspv":
        return semiring_spmspv_padded, (tiles, meta, x)
    assert name == "spmspv_fused", name
    return semiring_spmspv_fused_padded, (tiles, meta, x)


@pytest.mark.parametrize("sr", VECTOR_SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("name", ["spmv", "spmv_fused", "spmv_fused_chunked",
                                  "spmv_sell", "spmspv", "spmspv_fused"])
def test_vector_kernel_compiles_for_v5e(one_chip, name, sr):
    fn, args = _kernel_args(name, sr, one_chip)
    kw = {"sr": sr, "interpret": False}
    if name == "spmv_fused_chunked":
        kw["chunks"] = 4
    _compile(jax.jit(lambda *a: fn(*a, **kw)), *args)


@pytest.mark.parametrize("sr", VECTOR_SEMIRINGS + [PLUS_AND],
                         ids=lambda s: s.name)
def test_spgemm_kernel_compiles_for_v5e(one_chip, sr):
    nb = 4                       # output tile columns
    args = (_arg((MB, T, B, B), sr.dtype, one_chip),
            _arg((MB, T + nb), jnp.int32, one_chip),
            _arg((MB * B, nb * B), sr.dtype, one_chip),
            _arg((MB * B, nb * B), sr.dtype, one_chip))
    _compile(jax.jit(lambda *a: semiring_spgemm_padded(
        *a, sr=sr, bn=B, interpret=False)), *args)


def test_bfs_runner_compiles_for_v5e(one_chip):
    """The served BFS bucket runner compiles for the chip with the graph
    among its arguments (tests/test_multi_query.py checks on the CPU that
    the program's size does not grow with the graph)."""
    from repro.graphs.datasets import rmat_graph
    from repro.graphs.engine import build_engine
    from repro.graphs.multi import make_bfs_multi

    g = rmat_graph(n=1 << 12, n_edges=16 << 12, skew=0.57, seed=3)
    eng = build_engine(g, BOOL_OR_AND)
    run = make_bfs_multi(eng, batch=8)
    mats = jax.tree.map(lambda a: _arg(a.shape, a.dtype, one_chip), eng.mats)
    src = _arg((8,), jnp.int32, one_chip)
    compiled = run.jitted.lower(mats, src).compile()
    mem = compiled.memory_analysis()
    # the SpMV matrix's per-entry arrays (CSR cols/vals/seg ids) arrive as
    # arguments
    csr = eng.mats[0]
    entry_bytes = sum(a.size * a.dtype.itemsize
                      for a in (csr.cols, csr.vals, csr.seg_ids))
    assert mem.argument_size_in_bytes >= entry_bytes


@pytest.mark.parametrize("sr", [BOOL_OR_AND, MIN_PLUS], ids=lambda s: s.name)
def test_csr_spmv_batch_has_no_scatter_for_v5e(one_chip, sr):
    """The batched CSR SpMV, graph as arguments, reduces its row-sorted
    products by the segmented scan: the compiled program holds no scatter
    and no sort, and the scan runs with the entries in the lanes."""
    n, nnz = 1 << 14, 1 << 19
    a = CSRMatrix(_arg((n + 1,), jnp.int32, one_chip),
                  _arg((nnz,), jnp.int32, one_chip),
                  _arg((nnz,), sr.dtype, one_chip),
                  _arg((nnz,), jnp.int32, one_chip),
                  _arg((), jnp.int32, one_chip), (n, n), max_row_nnz=5000)
    xs = _arg((8, n), sr.dtype, one_chip)
    text = jax.jit(lambda a, xs: spmv_batch(a, xs, sr)).lower(
        a, xs).compile().as_text()
    assert "scatter(" not in text and "sort(" not in text
    steps = [ln for ln in text.splitlines()
             if "row_scan" in ln and " fusion(" in ln and f"[8,{nnz}]" in ln]
    assert len(steps) >= a.scan_steps - 1, len(steps)
    assert all(f"[8,{nnz}]{{1,0" in ln for ln in steps), steps[0]

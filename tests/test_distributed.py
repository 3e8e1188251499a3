"""Distributed engine tests. Multi-device CPU runs need
XLA_FLAGS=--xla_force_host_platform_device_count set *before* jax import,
so these run in subprocesses (the main pytest process keeps 1 device)."""
import os
import subprocess
import sys

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.distributed import make_distributed_matvec

rng = np.random.default_rng(1)
n = 128
dense_np = (rng.random((n, n)) < 0.08).astype(np.float32) * rng.integers(1, 9, (n, n))
rows, cols = np.nonzero(dense_np)
vals = dense_np[rows, cols].astype(np.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("dr", "dc"))

checked = 0
for sr in (PLUS_TIMES, MIN_PLUS, BOOL_OR_AND):
    if sr.name == "min_plus":
        dense = np.where(dense_np != 0, dense_np, np.inf).astype(np.float32)
        x = np.where(rng.random(n) < 0.3, rng.random(n), np.inf).astype(np.float32)
        v = vals; fill = np.inf
    elif sr.name == "bool_or_and":
        dense = (dense_np != 0).astype(np.int32)
        x = (rng.random(n) < 0.3).astype(np.int32)
        v = np.ones_like(vals, dtype=np.int32); fill = 0
    else:
        dense = dense_np
        x = np.where(rng.random(n) < 0.3, rng.random(n), 0).astype(np.float32)
        v = vals; fill = 0.0
    oracle = np.asarray(sr.matvec(jnp.asarray(dense, sr.dtype), jnp.asarray(x, sr.dtype)))

    cases = [("row", (8, 1), "csr", "spmv"), ("row", (8, 1), "coo", "spmv"),
             ("col", (1, 8), "csc", "spmspv"), ("2d", (2, 4), "csc", "spmspv"),
             ("2d", (2, 4), "coo", "spmv"), ("row", (8, 1), "bsr", "spmv"),
             ("2d", (2, 4), "bsr", "spmspv")]
    for strategy, grid, fmt, kern in cases:
        for balance in ("rows", "nnz"):
            pm = partition(rows, cols, v, (n, n), grid, fmt, sr,
                           block=(16, 16), balance=balance)
            xs = jnp.asarray(pm.plan.shard_input_vector(x, fill), sr.dtype)
            fn = make_distributed_matvec(mesh, pm, sr, strategy, kernel=kern)
            y = pm.plan.unshard_output_vector(
                np.asarray(jax.jit(fn)(pm.parts, xs)))
            np.testing.assert_allclose(
                y, oracle, rtol=1e-5,
                err_msg=f"{sr.name}/{strategy}/{fmt}/{kern}/{balance}")
            checked += 1
print(f"DISTRIBUTED_OK {checked}")
"""


@pytest.mark.slow
def test_distributed_strategies_8dev():
    """Every Fig.-3 strategy × format × balance mode must match the dense
    semiring oracle — nnz-balanced plans included (ISSUE-4 acceptance:
    planner-partitioned results equal the unpartitioned reference)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", WORKER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "DISTRIBUTED_OK 42" in res.stdout, res.stdout


BATCHED_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.distributed import make_distributed_batched_matvec

rng = np.random.default_rng(2)
n, B = 128, 4
dense_np = (rng.random((n, n)) < 0.08).astype(np.float32) * rng.integers(1, 9, (n, n))
rows, cols = np.nonzero(dense_np)
vals = dense_np[rows, cols].astype(np.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("dr", "dc"))

checked = 0
for sr in (PLUS_TIMES, MIN_PLUS, BOOL_OR_AND):
    if sr.name == "min_plus":
        dense = np.where(dense_np != 0, dense_np, np.inf).astype(np.float32)
        X = np.where(rng.random((B, n)) < 0.3, rng.random((B, n)), np.inf).astype(np.float32)
        v = vals; fill = np.inf
    elif sr.name == "bool_or_and":
        dense = (dense_np != 0).astype(np.int32)
        X = (rng.random((B, n)) < 0.3).astype(np.int32)
        v = np.ones_like(vals, dtype=np.int32); fill = 0
    else:
        dense = dense_np
        X = np.where(rng.random((B, n)) < 0.3, rng.random((B, n)), 0).astype(np.float32)
        v = vals; fill = 0.0
    oracle = np.stack([np.asarray(sr.matvec(jnp.asarray(dense, sr.dtype),
                                            jnp.asarray(x, sr.dtype))) for x in X])
    for strategy, grid, fmt, kern in [("row", (8, 1), "csr", "spmv"),
                                      ("col", (1, 8), "csc", "spmspv"),
                                      ("2d", (2, 4), "csc", "spmspv"),
                                      ("2d", (2, 4), "coo", "spmv")]:
        for balance in ("rows", "nnz"):
            pm = partition(rows, cols, v, (n, n), grid, fmt, sr,
                           balance=balance)
            xs = jnp.asarray(pm.plan.shard_input_batch(X, fill), sr.dtype)
            fn = make_distributed_batched_matvec(mesh, pm, sr, strategy,
                                                 kernel=kern)
            y = np.asarray(jax.jit(fn)(pm.parts, xs))
            yf = pm.plan.unshard_output_batch(y)
            np.testing.assert_allclose(
                yf, oracle, rtol=1e-5,
                err_msg=f"{sr.name}/{strategy}/{fmt}/{kern}/{balance}")
            checked += 1
print(f"BATCHED_DISTRIBUTED_OK {checked}")
"""


@pytest.mark.slow
def test_distributed_batched_matvec_8dev():
    """[B, n]-block matvec over the Fig.-3 partitioning strategies × balance
    modes: every row must match the dense semiring oracle (the multi-query
    mesh path)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", BATCHED_WORKER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "BATCHED_DISTRIBUTED_OK 24" in res.stdout, res.stdout


AUTO_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core.semiring import PLUS_TIMES
from repro.graphs.datasets import rmat_graph, road_graph
from repro.graphs.engine import edge_values
from repro.graphs.multi import partitioned_matvec

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("dr", "dc"))
checked = 0
for g in (rmat_graph(700, 5000, skew=0.6, seed=2),
          road_graph(900, 2.6, seed=2)):
    sr = PLUS_TIMES
    rng = np.random.default_rng(0)
    for spec, kern in [("auto", "spmv"), ("row:nnz", "spmv"),
                       ("col", "spmspv"), ("2d:nnz", "spmspv")]:
        pm, fn, choice = partitioned_matvec(g, sr, mesh, strategy=spec,
                                            kernel=kern)
        n_pad = pm.plan.shape[1]
        dense = np.zeros((n_pad, n_pad), np.float32)
        dense[g.cols, g.rows] = edge_values(g, sr, False, 0, False)
        x = np.where(rng.random(n_pad) < 0.4, rng.random(n_pad), 0
                     ).astype(np.float32)
        xs = jnp.asarray(pm.plan.shard_input_vector(x, 0.0), sr.dtype)
        y = pm.plan.unshard_output_vector(np.asarray(jax.jit(fn)(pm.parts, xs)))
        np.testing.assert_allclose(y, dense @ x, rtol=1e-4,
                                   err_msg=f"{g.name}/{spec}")
        # the pick is never more skewed than the worst candidate it saw
        worst = max(c["imbalance"] for c in choice.costs.values())
        assert choice.plan.imbalance() <= worst + 1e-9
        checked += 1
print(f"AUTO_PLANNER_OK {checked}")
"""


@pytest.mark.slow
def test_auto_planner_partitioned_matvec_8dev():
    """graphs.multi.partitioned_matvec: the cost-model planner's auto pick
    (and fixed strategy:balance specs) must run on the mesh and match the
    dense oracle, with the chosen plan never more skewed than the worst
    candidate."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", AUTO_WORKER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "AUTO_PLANNER_OK 8" in res.stdout, res.stdout


PIPELINE_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.distributed import build_phase_fns
from repro.core.pipeline import iterate_phases

rng = np.random.default_rng(3)
n = 128
dense_np = (rng.random((n, n)) < 0.08).astype(np.float32) * rng.integers(1, 9, (n, n))
rows, cols = np.nonzero(dense_np)
vals = dense_np[rows, cols].astype(np.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("dr", "dc"))

checked = 0
for sr in (PLUS_TIMES, MIN_PLUS, BOOL_OR_AND):
    if sr.name == "min_plus":
        dense = np.where(dense_np != 0, dense_np, np.inf).astype(np.float32)
        x = np.where(rng.random(n) < 0.3, rng.random(n), np.inf).astype(np.float32)
        v = vals; fill = np.inf
    elif sr.name == "bool_or_and":
        dense = (dense_np != 0).astype(np.int32)
        x = (rng.random(n) < 0.3).astype(np.int32)
        v = np.ones_like(vals, dtype=np.int32); fill = 0
    else:
        dense = dense_np
        x = np.where(rng.random(n) < 0.3, rng.random(n), 0).astype(np.float32)
        v = vals; fill = 0.0
    xo = jnp.asarray(x, sr.dtype)        # 4-iteration dense oracle
    for _ in range(4):
        xo = sr.matvec(jnp.asarray(dense, sr.dtype), xo)
    oracle = np.asarray(xo)
    for strategy, grid, fmt, kern in [("row", (8, 1), "csr", "spmv"),
                                      ("col", (1, 8), "csc", "spmspv"),
                                      ("2d", (2, 4), "csc", "spmspv"),
                                      ("2d", (2, 4), "coo", "spmv")]:
        pm = partition(rows, cols, v, (n, n), grid, fmt, sr)
        n_pad = pm.shape[1]
        xp = np.full(n_pad, fill, dtype=x.dtype); xp[:n] = x
        xs = jnp.asarray(xp.reshape(8, -1), sr.dtype)
        fns = build_phase_fns(mesh, pm, sr, strategy, kern)
        y_blocking = iterate_phases(fns, pm.parts, xs, 4, depth=0)
        for depth in (1, 3):
            y_pip = iterate_phases(fns, pm.parts, xs, 4, depth=depth)
            np.testing.assert_array_equal(
                np.asarray(y_blocking), np.asarray(y_pip),
                err_msg=f"{sr.name}/{strategy}/{fmt}/{kern}/depth{depth}")
        if strategy == "col":
            # donate=True (R+M buffer reuse; no-op on CPU backends) must
            # not change results either
            fns_don = build_phase_fns(mesh, pm, sr, strategy, kern, donate=True)
            y_don = iterate_phases(fns_don, pm.parts, xs, 4, depth=2)
            np.testing.assert_array_equal(np.asarray(y_blocking), np.asarray(y_don))
        got = np.asarray(y_blocking).reshape(-1)[:n]
        np.testing.assert_allclose(got, oracle, rtol=1e-5,
                                   err_msg=f"{sr.name}/{strategy}/{fmt}/{kern}")
        checked += 1
print(f"PIPELINE_OK {checked}")
"""


@pytest.mark.slow
def test_pipelined_iteration_matches_blocking_8dev():
    """core.pipeline.iterate_phases: the pipelined schedule (depths 1 and
    3) must be bit-identical to the depth-0 blocking fallback for every
    Fig.-3 strategy and traversal semiring, and both must match a dense
    4-iteration oracle — the non-blocking-DMA model changes wall time,
    never results."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", PIPELINE_WORKER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert "PIPELINE_OK 12" in res.stdout, res.stdout


COLLECTIVES_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.distributed import make_distributed_matvec

rng = np.random.default_rng(6)
n = 128
dense_np = (rng.random((n, n)) < 0.08).astype(np.float32) * rng.integers(1, 9, (n, n))
rows, cols = np.nonzero(dense_np)
vals = dense_np[rows, cols].astype(np.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("dr", "dc"))

checked = 0
for sr in (PLUS_TIMES, MIN_PLUS, PLUS_AND):
    if sr.name == "min_plus":
        dense = np.where(dense_np != 0, dense_np, np.inf).astype(np.float32)
        x = np.where(rng.random(n) < 0.3, rng.integers(0, 9, n), np.inf).astype(np.float32)
        v = vals; fill = np.inf
    elif sr.name == "plus_and":
        dense = (dense_np != 0).astype(np.int32)
        x = (rng.random(n) < 0.3).astype(np.int32)
        v = np.ones_like(vals, dtype=np.int32); fill = 0
    else:
        dense = dense_np
        x = rng.integers(0, 9, n).astype(np.float32)   # integer-valued:
        v = vals; fill = 0.0                           # ⊕ order-exact
    oracle = np.asarray(sr.matvec(jnp.asarray(dense, sr.dtype),
                                  jnp.asarray(x, sr.dtype)))
    for strategy, grid in [("row", (8, 1)), ("col", (1, 8)), ("2d", (2, 4))]:
        for balance in ("rows", "nnz"):
            pm = partition(rows, cols, v, (n, n), grid, "csr", sr,
                           balance=balance)
            xs = jnp.asarray(pm.plan.shard_input_vector(x, fill), sr.dtype)
            y_flat = None
            topos = [("flat", "rc"), ("ring", "rc"), ("tree", "rc"),
                     ("staged2d", "rc")]
            if strategy == "col":
                topos.append(("staged2d", "cr"))
            for topology, order in topos:
                fn = make_distributed_matvec(mesh, pm, sr, strategy,
                                             topology=topology,
                                             merge_order=order)
                y = pm.plan.unshard_output_vector(
                    np.asarray(jax.jit(fn)(pm.parts, xs)))
                tag = f"{sr.name}/{strategy}/{balance}/{topology}:{order}"
                np.testing.assert_array_equal(y, oracle, err_msg=tag)
                if y_flat is None:
                    y_flat = y
                else:   # bit-identical to the flat merge, not just close
                    np.testing.assert_array_equal(y, y_flat, err_msg=tag)
                checked += 1
print(f"COLLECTIVES_OK {checked}")
"""


@pytest.mark.slow
def test_merge_collectives_bit_equal_8dev():
    """core.collectives: ring/tree/staged-2D merges must be bit-identical
    to the flat merge AND the dense oracle for every strategy x balance x
    semiring (psum, pmin, and the plus_and counting semiring) — integer
    data makes every ⊕ order exact, so equality is == not allclose."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", COLLECTIVES_WORKER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    # 3 semirings x (row,col,2d) x 2 balances x 4 topologies (+1 cr on col)
    assert "COLLECTIVES_OK 78" in res.stdout, res.stdout


COLLECTIVES_NPO2_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=12"
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.distributed import make_distributed_matvec

rng = np.random.default_rng(9)
n = 192    # divisible by 12 for the col strategy's flat-axis chunks
dense_np = (rng.random((n, n)) < 0.06).astype(np.float32) * rng.integers(1, 9, (n, n))
rows, cols = np.nonzero(dense_np)
vals = dense_np[rows, cols].astype(np.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 3), ("dr", "dc"))   # dc=3: odd-radix merge axis

checked = 0
for sr in (PLUS_TIMES, MIN_PLUS):
    if sr.name == "min_plus":
        dense = np.where(dense_np != 0, dense_np, np.inf).astype(np.float32)
        x = np.where(rng.random(n) < 0.3, rng.integers(0, 9, n), np.inf).astype(np.float32)
        v = vals; fill = np.inf
    else:
        dense = dense_np
        x = rng.integers(0, 9, n).astype(np.float32)
        v = vals; fill = 0.0
    oracle = np.asarray(sr.matvec(jnp.asarray(dense, sr.dtype),
                                  jnp.asarray(x, sr.dtype)))
    for strategy, grid in [("col", (1, 12)), ("2d", (4, 3))]:
        pm = partition(rows, cols, v, (n, n), grid, "csr", sr, balance="nnz")
        xs = jnp.asarray(pm.plan.shard_input_vector(x, fill), sr.dtype)
        y_flat = None
        topos = [("flat", "rc"), ("ring", "rc"), ("tree", "rc"),
                 ("staged2d", "rc")]
        if strategy == "col":
            topos.append(("staged2d", "cr"))
        for topology, order in topos:
            fn = make_distributed_matvec(mesh, pm, sr, strategy,
                                         topology=topology,
                                         merge_order=order)
            y = pm.plan.unshard_output_vector(
                np.asarray(jax.jit(fn)(pm.parts, xs)))
            tag = f"{sr.name}/{strategy}/{topology}:{order}"
            np.testing.assert_array_equal(y, oracle, err_msg=tag)
            if y_flat is None:
                y_flat = y
            else:
                np.testing.assert_array_equal(y, y_flat, err_msg=tag)
            checked += 1
print(f"COLLECTIVES_NPO2_OK {checked}")
"""


@pytest.mark.slow
def test_merge_collectives_12dev_non_power_of_two():
    """12 devices on a (4, 3) mesh — past the 8-device workers and with a
    non-power-of-two merge axis: the tree schedule gets a factor-3 radix
    stage (col: 12 = 2*2*3; 2d: the dc=3 axis) and the 12-hop ring /
    staged exchanges must still land chunk g on device g, bit-identical
    to the flat merge and the dense oracle."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", COLLECTIVES_NPO2_WORKER],
                         env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    # 2 semirings x (col: 5 topologies + 2d: 4 topologies)
    assert "COLLECTIVES_NPO2_OK 18" in res.stdout, res.stdout


FUSED_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import *
from repro.core.distributed import build_phase_fns, make_distributed_matvec
from repro.core.pipeline import run_phases_once

rng = np.random.default_rng(5)
n = 128
dense_np = (rng.random((n, n)) < 0.08).astype(np.float32) * rng.integers(1, 9, (n, n))
rows, cols = np.nonzero(dense_np)
vals = dense_np[rows, cols].astype(np.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("dr", "dc"))

checked = 0
for sr in (PLUS_TIMES, MIN_PLUS, BOOL_OR_AND):
    if sr.name == "min_plus":
        x = np.where(rng.random(n) < 0.4, rng.integers(0, 9, n), np.inf).astype(np.float32)
        v = vals; fill = np.inf
    elif sr.name == "bool_or_and":
        x = (rng.random(n) < 0.4).astype(np.int32)
        v = np.ones_like(vals, dtype=np.int32); fill = 0
    else:
        x = np.where(rng.random(n) < 0.4, rng.integers(0, 9, n), 0).astype(np.float32)
        v = vals; fill = 0.0
    for strategy, grid in (("row", (8, 1)), ("col", (1, 8)), ("2d", (2, 4))):
        pm = partition(rows, cols, v, (n, n), grid, "bsr", sr, block=(16, 16))
        xs = jnp.asarray(pm.plan.shard_input_vector(x, fill), sr.dtype)
        for topology in ("flat", "ring", "tree"):
            # e2e: fused must be bit-identical to its unfused ancestor
            y_u = pm.plan.unshard_output_vector(np.asarray(jax.jit(
                make_distributed_matvec(mesh, pm, sr, strategy,
                                        topology=topology))(pm.parts, xs)))
            y_f = pm.plan.unshard_output_vector(np.asarray(jax.jit(
                make_distributed_matvec(mesh, pm, sr, strategy,
                                        topology=topology,
                                        fused=True))(pm.parts, xs)))
            np.testing.assert_array_equal(
                y_f, y_u, err_msg=f"{sr.name}/{strategy}/{topology}")
            checked += 1
        # phase closures: fused folds Retrieve+Merge into the kernel
        fns_u = build_phase_fns(mesh, pm, sr, strategy, kernel="spmv")
        fns_f = build_phase_fns(mesh, pm, sr, strategy, kernel="spmv",
                                fused=True)
        if strategy != "row":
            assert fns_f["retrieve_merge"] is None, strategy
        y_pu = np.asarray(run_phases_once(fns_u, pm.parts, xs))
        y_pf = np.asarray(run_phases_once(fns_f, pm.parts, xs))
        np.testing.assert_array_equal(y_pf, y_pu,
                                      err_msg=f"phases/{sr.name}/{strategy}")
        checked += 1

# fused demands the ELL-of-tiles stream: any other format must refuse
pm = partition(rows, cols, vals, (n, n), (1, 8), "csc", PLUS_TIMES)
try:
    make_distributed_matvec(mesh, pm, PLUS_TIMES, "col", fused=True)
    raise SystemExit("fused accepted a csc partition")
except ValueError:
    checked += 1
print(f"FUSED_OK {checked}")
"""


@pytest.mark.slow
def test_fused_distributed_bit_identical_8dev():
    """The fused Load+Kernel(+Retrieve+Merge) path must be bit-identical
    to the unfused four-phase ancestor for every strategy x topology x
    semiring, both through make_distributed_matvec and through the
    build_phase_fns closures (whose fused dicts fold retrieve_merge away),
    and must reject non-BSR partitions."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", FUSED_WORKER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    # 3 semirings x 3 strategies x (3 topologies + 1 phase check) + 1 raise
    assert "FUSED_OK 37" in res.stdout, res.stdout

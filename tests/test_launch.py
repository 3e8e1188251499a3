"""Launch layer: HLO structural analyzer against known-answer modules, mesh
builders, dry-run record schema (one fast cell in a subprocess), and the
distributed train-step (compressed pod gradients) on a small mesh."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
REPO = os.path.join(os.path.dirname(__file__), "..")


ANALYZER_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo_analysis as H

from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
L, B, D = 8, 16, 256
W = jax.ShapeDtypeStruct((L, D, D), jnp.bfloat16)   # cols model-sharded
X = jax.ShapeDtypeStruct((B, D), jnp.bfloat16)      # rows data-sharded

# shard_map pins the per-device computation exactly (the pure-pjit version
# left the partitioning to XLA's SPMD cost model, which changes across
# releases); each device scans L dots of [B/2, D] @ [D, D/4].
def f(ws, x):
    def body(acc, w):
        y = x @ w
        return acc + y.astype(jnp.float32).sum(), None
    acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), ws)
    return jax.lax.psum(acc, ("data", "model"))

fn = jax.shard_map(f, mesh=mesh,
                   in_specs=(P(None, None, "model"), P("data", None)),
                   out_specs=P(), check_vma=False)
co = jax.jit(fn).lower(W, X).compile()
ana = H.analyze(co.as_text(), 8, pod_size=256)
# per-device dot flops: L * 2 * (B/2) * D * (D/4)
want = L * 2 * (B // 2) * D * (D // 4)
assert abs(ana.flops - want) / want < 0.02, (ana.flops, want)
assert ana.unknown_trip_loops == 0
assert ana.wire_bytes > 0 and ana.dcn_bytes == 0
terms = H.roofline_terms(ana)
assert terms["compute_s"] > 0 and terms["dominant"] in ("compute", "memory", "collective")

# multi-pod mesh: the pod-axis collective must be classified as DCN
mesh2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
def g(x):
    return x.sum()
co2 = jax.jit(g, in_shardings=(
    NamedSharding(mesh2, P(("pod", "data"))),),
    out_shardings=NamedSharding(mesh2, P())).lower(
    jax.ShapeDtypeStruct((32, 64), jnp.float32)).compile()
ana2 = H.analyze(co2.as_text(), 8, pod_size=4)  # pods of 4 devices
assert ana2.dcn_bytes > 0, "pod-crossing all-reduce must be DCN"
print("ANALYZER_OK")
"""


@pytest.mark.slow
def test_hlo_analyzer_known_answers():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", ANALYZER_WORKER], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ANALYZER_OK" in res.stdout


@pytest.mark.slow
def test_dryrun_one_cell_subprocess(tmp_path):
    """Full production-mesh dry-run of the fastest cell; validates the
    record schema EXPERIMENTS.md §Dry-run consumes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-1.3b",
         "--shape", "decode_32k", "--mesh", "single", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=1200, cwd=REPO)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    rec = json.load(open(tmp_path / "xlstm-1.3b__decode_32k__single.json"))
    assert rec["devices"] == 256
    for key in ("compute_s", "memory_s", "collective_s", "dominant"):
        assert key in rec["roofline"]
    assert rec["cost"]["flops_per_device"] > 0
    assert rec["collectives"]["unknown_trip_loops"] == 0


COMPRESSED_STEP_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import small_mesh
from repro.models.transformer import build_model
from repro.models.zoo import reduced_config
from repro.train.data import DataConfig, SyntheticLM
from repro.train.grad_compress import ef_init
from repro.train.optimizer import OptConfig, adamw_init
from repro.train.train_loop import (
    TrainConfig, make_compressed_train_step, make_train_step)

cfg = dataclasses.replace(reduced_config("minitron-4b", 0.05), n_layers=2)
model = build_model(cfg)
mesh = small_mesh(data=2, model=2, pod=2)
tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=10))
params = model.init(jax.random.PRNGKey(0))
opt = adamw_init(params)
ef = ef_init(params)
src = SyntheticLM(DataConfig(global_batch=8, seq_len=16, vocab=cfg.vocab))

step_c = make_compressed_train_step(model, mesh, tcfg)
step_p = make_train_step(model, mesh, tcfg, donate=False)
p_c, o_c, p_p, o_p = params, opt, params, opt
for i in range(5):
    b = {k: jnp.asarray(v) for k, v in src.batch(i, 0, 1).items()}
    p_c, o_c, ef, m_c = step_c(p_c, o_c, ef, b)
    p_p, o_p, m_p = step_p(p_p, o_p, b)
# int8-compressed pod gradients stay close to the exact pjit step
for a, b_ in zip(jax.tree.leaves(p_c), jax.tree.leaves(p_p)):
    d = np.abs(np.asarray(a, np.float32) - np.asarray(b_, np.float32))
    r = np.abs(np.asarray(b_, np.float32)) + 1e-3
    assert (d / r).mean() < 0.05, (d / r).mean()
assert abs(float(m_c["loss"]) - float(m_p["loss"])) < 0.05 * abs(float(m_p["loss"]))
print("COMPRESSED_OK")
"""


@pytest.mark.slow
def test_compressed_pod_gradients_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", COMPRESSED_STEP_WORKER],
                         env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-3000:]
    assert "COMPRESSED_OK" in res.stdout


def test_mesh_builders():
    # shapes only (make_mesh would need 256+ devices; the dry-run covers it)
    from repro.models.config import SHAPES
    assert SHAPES["train_4k"].tokens == 4096 * 256
    assert SHAPES["long_500k"].seq_len == 524288


def test_compressed_frontier_gather_math():
    """gather_frontier offset math (host-side check of the index layout)."""
    from repro.core.semiring import PLUS_TIMES
    from repro.core.spmspv import frontier_from_dense
    x = np.zeros(16, np.float32)
    x[[1, 5]] = 2.0
    f = frontier_from_dense(np.asarray(x), PLUS_TIMES, f_max=4)
    idx = np.asarray(f.indices)
    assert set(idx[idx < 16]) == {1, 5}
    assert int(f.count) == 2

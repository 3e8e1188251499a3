"""Fault tolerance: checkpoint roundtrip, failure-injected restart
reproducing the uninterrupted run bitwise, elastic mesh rescale, straggler
policy logic."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.fault_tolerance import (
    FTConfig, StragglerMonitor, TrainDriver,
)
from repro.models.zoo import reduced_config
from repro.models.transformer import build_model
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, SyntheticLM
from repro.train.optimizer import OptConfig, adamw_init
from repro.train.train_loop import TrainConfig, train_step_fn

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def setup(tmp_path, ckpt_every=4):
    import dataclasses
    cfg = dataclasses.replace(reduced_config("minitron-4b", 0.05), n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=2, total_steps=20))
    step = jax.jit(train_step_fn(model, tcfg))
    src = SyntheticLM(DataConfig(global_batch=4, seq_len=16, vocab=cfg.vocab))

    def batch_fn(i):
        return {k: jnp.asarray(v) for k, v in src.batch(i, 0, 1).items()}

    driver = TrainDriver(step, batch_fn,
                         FTConfig(ckpt_dir=str(tmp_path), ckpt_every=ckpt_every,
                                  async_save=False))
    return params, opt, driver


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray([1, 2], jnp.int32)}}
    ckpt.save(str(tmp_path), 5, tree, metadata={"note": "x"})
    assert ckpt.latest_step(str(tmp_path)) == 5
    got, meta = ckpt.restore(str(tmp_path), 5, tree)
    assert meta == {"note": "x"}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restart_reproduces_uninterrupted_run(tmp_path):
    """Injected failures + restore => bitwise-identical loss history
    (deterministic (seed, step, shard) batches make recovery exact)."""
    p1, o1, d_clean = setup(tmp_path / "clean")
    clean = d_clean.run(p1, o1, 12)
    p2, o2, d_fail = setup(tmp_path / "faulty")
    faulty = d_fail.run(p2, o2, 12, failure_at=[5, 9])
    assert faulty["restarts"] == 2
    c = {h["step"]: h["loss"] for h in clean["history"]}
    f = {h["step"]: h["loss"] for h in faulty["history"]}
    for s in range(12):
        assert c[s] == f[s], (s, c[s], f[s])
    for a, b in zip(jax.tree.leaves(clean["params"]),
                    jax.tree.leaves(faulty["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_monitor_flags_and_paces():
    m = StragglerMonitor(factor=2.0, max_lag=2)
    for step in range(8):
        m.record(0, step, 0.10)
        m.record(1, step, 0.11)
        m.record(2, step, 0.55)     # straggler
    assert m.stragglers() == [2]
    assert not m.must_resync()
    m.progress[2] = 2               # falls 6 steps behind
    m.progress[0] = m.progress[1] = 8
    assert m.must_resync()


ELASTIC_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, sys
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.sharding import param_shardings
from repro.models.transformer import build_model
from repro.models.zoo import reduced_config
from repro.train import checkpoint as ckpt

cfg = dataclasses.replace(reduced_config("minitron-4b", 0.05), n_layers=2)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
path = sys.argv[1]

from repro.launch.mesh import make_mesh
mesh_a = make_mesh((2, 2), ("data", "model"))
sh_a = param_shardings(mesh_a, model.specs())
params_a = jax.tree.map(jax.device_put, params, sh_a)
ckpt.save(path, 1, {"params": params_a})

# elastic rescale: restore the (2,2) checkpoint onto a (4,1)... and (1,8) mesh
for shape in [(4, 1), (1, 8)]:
    mesh_b = make_mesh(shape, ("data", "model"))
    sh_b = param_shardings(mesh_b, model.specs())
    got, _ = ckpt.restore(path, 1, {"params": params}, {"params": sh_b})
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(got["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    leaf = jax.tree.leaves(got["params"])[0]
    assert len(leaf.sharding.device_set) == shape[0] * shape[1]
print("ELASTIC_OK")
"""


@pytest.mark.slow
def test_elastic_rescale_subprocess(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-c", ELASTIC_WORKER, str(tmp_path)], env=env,
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ELASTIC_OK" in res.stdout

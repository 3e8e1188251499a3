"""Distributed train step builders.

Two flavors share the same loss/optimizer plumbing:

* ``make_train_step``      — pure pjit: XLA inserts every collective
  (gradient reduction over (pod, data) is implicit in the backward pass).
* ``make_compressed_train_step`` — the pod (DCN) axis goes *manual* via
  shard_map(axis_names={"pod"}); gradients cross pods as error-feedback
  int8 (train/grad_compress.py) while ICI-side sharding stays automatic.

Microbatch gradient accumulation: the global batch is split into
``microbatches`` slices scanned sequentially — activation memory scales
with the slice, not the global batch (how the train_4k cells fit HBM).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import (
    constrain_batch_tree, param_shardings, set_activation_mesh,
    zero1_shardings,
)
from repro.models.transformer import Model
from repro.train.grad_compress import compressed_tree_psum_mean
from repro.train.optimizer import OptConfig, OptState, adamw_apply, adamw_init

Array = jax.Array


def partial_shard_map(body, mesh: Mesh, manual_axes, in_specs, out_specs):
    """shard_map that is manual only over ``manual_axes``; the remaining mesh
    axes stay automatic (SPMD-partitioned)."""
    return jax.shard_map(body, mesh=mesh, axis_names=set(manual_axes),
                         check_vma=False, in_specs=in_specs,
                         out_specs=out_specs)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: OptConfig = OptConfig()
    microbatches: int = 1
    remat: bool = True
    grad_compress_pod: bool = False   # int8 EF compression on the pod axis


def _split_micro(batch, k: int):
    """[GB, ...] -> [k, GB/k, ...] per leaf."""
    return jax.tree.map(
        lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)


def _grads_and_loss(model: Model, params, batch, cfg: TrainConfig):
    def loss_fn(p, mb):
        loss, aux = model.loss(p, mb, remat=cfg.remat)
        return loss, aux

    if cfg.microbatches <= 1:
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return grads, loss, aux

    micro = _split_micro(batch, cfg.microbatches)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        acc, loss_acc = carry
        # re-pin the microbatch's batch sharding: XLA's propagation through
        # the [k, GB/k, ...] reshape otherwise replicates it (probed)
        mb = constrain_batch_tree(mb)
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
        return (acc, loss_acc + loss), None

    (gsum, loss_sum), _ = jax.lax.scan(body, (zeros, 0.0), micro)
    k = cfg.microbatches
    grads = jax.tree.map(lambda g: g / k, gsum)
    loss = loss_sum / k
    return grads, loss, {"loss": loss}


def train_step_fn(model: Model, cfg: TrainConfig):
    """The undistributed step body: (params, opt_state, batch) -> ..."""

    def step(params, opt_state: OptState, batch):
        grads, loss, _ = _grads_and_loss(model, params, batch, cfg)
        params, opt_state, om = adamw_apply(params, grads, opt_state, cfg.opt)
        metrics = {"loss": loss, **om}
        return params, opt_state, metrics

    return step


def batch_sharding(mesh: Mesh, batch_specs) -> Any:
    """Shard every batch leaf's leading (global-batch) dim over (pod, data)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(leaf):
        nd = len(leaf.shape)
        return NamedSharding(mesh, P(axes, *([None] * (nd - 1))))

    return jax.tree.map(one, batch_specs)


def make_train_step(model: Model, mesh: Mesh, cfg: TrainConfig,
                    donate: bool = True):
    """jit'd pjit train step with params/opt-state/batch shardings attached."""
    set_activation_mesh(mesh)
    specs = model.specs()
    p_sh = param_shardings(mesh, specs)
    opt_sh = OptState(
        step=NamedSharding(mesh, P()),
        master=zero1_shardings(mesh, specs),
        mu=zero1_shardings(mesh, specs),
        nu=zero1_shardings(mesh, specs),
    )
    step = train_step_fn(model, cfg)
    return jax.jit(
        step,
        in_shardings=(p_sh, opt_sh, None),
        out_shardings=(p_sh, opt_sh, None),
        donate_argnums=(0, 1) if donate else (),
    )


def make_compressed_train_step(model: Model, mesh: Mesh, cfg: TrainConfig):
    """Pod-axis-manual variant: per-pod grads -> int8 EF all-gather across
    pods -> identical optimizer step on every pod.

    State adds an error-feedback buffer tree (f32, param-shaped)."""
    assert "pod" in mesh.axis_names, "compressed step needs a pod axis"
    set_activation_mesh(mesh)
    specs = model.specs()
    p_sh = param_shardings(mesh, specs)
    opt_sh = OptState(
        step=NamedSharding(mesh, P()),
        master=zero1_shardings(mesh, specs),
        mu=zero1_shardings(mesh, specs),
        nu=zero1_shardings(mesh, specs),
    )
    ef_sh = zero1_shardings(mesh, specs)

    def body(params, opt_state, ef, batch):
        # trace WITHOUT activation constraints: XLA's SPMD partitioner
        # CHECK-crashes on with_sharding_constraint specs inside a
        # partial-manual (pod) shard_map (probed, spmd_partitioner_util
        # device-group check); propagation alone is adequate per-pod.
        from repro.distributed.sharding import (
            activation_mesh, set_activation_mesh)
        prev = activation_mesh()
        set_activation_mesh(None)
        try:
            grads, loss, _ = _grads_and_loss(model, params, batch, cfg)
        finally:
            set_activation_mesh(prev)
        # mean over pods in int8 with error feedback (the DCN hop)
        grads, ef = compressed_tree_psum_mean(grads, ef, "pod")
        loss = jax.lax.pmean(loss, "pod")
        params, opt_state, om = adamw_apply(params, grads, opt_state, cfg.opt)
        return params, opt_state, ef, {"loss": loss, **om}

    shard_body = partial_shard_map(
        body, mesh, manual_axes={"pod"},
        in_specs=(P(), P(), P(), P("pod")),
        out_specs=(P(), P(), P(), P()),
    )

    return jax.jit(shard_body,
                   in_shardings=(p_sh, opt_sh, ef_sh, None),
                   out_shardings=(p_sh, opt_sh, ef_sh, None))


def init_train_state(model: Model, rng) -> tuple:
    params = model.init(rng)
    return params, adamw_init(params)

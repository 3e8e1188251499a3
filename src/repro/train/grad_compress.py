"""int8 error-feedback gradient compression for the pod (DCN) axis.

Multi-pod training reduces gradients over two fabrics: ICI within a pod
(~50 GB/s/link) and DCN between pods (~10x slower). The pod-axis reduction
therefore dominates multi-pod step time; compressing it 2x (bf16 -> int8)
halves the dominant collective term.

Scheme (1-bit-Adam-style error feedback, at 8 bits):
  x      = g + e          (carry quantization error across steps)
  q, s   = quantize(x)    (per-tensor symmetric int8, scale s = absmax/127)
  e'     = x - dequant(q) (error feedback)
  wire   = all_gather(q: int8) + all_gather(s)   over the pod axis
  result = mean_i dequant(q_i)

all_gather-of-int8 moves (n-1)/n * 1 byte/elem per link vs a bf16 ring
all-reduce's 2(n-1)/n * 2 bytes — a 4x wire-byte reduction, exact for the
pod=2 production mesh. The convergence contract (error feedback => unbiased
in the limit) is property-tested in tests/test_train.py.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def quantize_int8(x: Array) -> Tuple[Array, Array]:
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf)) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


def ef_init(params) -> Any:
    """Zero error-feedback buffers, shaped like the gradients (f32)."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_psum_mean(x: Array, ef: Array, axis_name: str
                         ) -> Tuple[Array, Array]:
    """Error-feedback int8 mean-reduction over ``axis_name``.

    Must run under shard_map with ``axis_name`` manual. Returns
    (mean-reduced f32 tensor, new error-feedback buffer)."""
    carry = x.astype(jnp.float32) + ef
    q, scale = quantize_int8(carry)
    new_ef = carry - dequantize_int8(q, scale)
    n = jax.lax.axis_size(axis_name)
    qg = jax.lax.all_gather(q, axis_name)        # [n, ...] int8 on the wire
    sg = jax.lax.all_gather(scale, axis_name)    # [n]
    deq = qg.astype(jnp.float32) * sg.reshape((n,) + (1,) * x.ndim)
    return jnp.sum(deq, axis=0) / n, new_ef


def compressed_tree_psum_mean(grads, ef_tree, axis_name: str):
    """Leaf-wise compressed mean-reduction of a gradient pytree."""
    pairs = jax.tree.map(
        lambda g, e: compressed_psum_mean(g, e, axis_name), grads, ef_tree)
    outer = jax.tree.structure(grads)
    inner = jax.tree.structure((0, 0))
    return jax.tree.transpose(outer, inner, pairs)

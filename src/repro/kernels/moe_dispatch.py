"""Pallas TPU kernel: MoE dispatch gather (beyond-paper, DESIGN.md §5).

Top-k routing is an SpMSpV: the dispatch matrix is one-hot-sparse with row
density k/E. On UPMEM this would be a per-column pointer chase; on TPU the
active "columns" are whole token rows, so the CSC active-column gather of
§4.1 becomes a scalar-prefetched row gather — the slot→token index map
plays exactly the role the paper's compressed input vector plays for
SpMSpV (only routed rows are DMA'd HBM→VMEM).

Layout:
    x        [T, D]        token activations (D a multiple of block_d)
    slot_tok i32 [S]       source token for each expert-capacity slot
                           (pad: T → slot is zeroed)
    out      [S, D]        gathered expert buffers (S = E * C, flattened)

Grid (S, D / block_d): slot i's row block j is DMA'd straight from token
slot_tok[i]'s row — no materialized one-hot, no scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tok_ref, x_ref, out_ref, *, n_tokens: int):
    i = pl.program_id(0)
    valid = tok_ref[i] < n_tokens
    row = x_ref[...]             # [1, block_d] — row chosen by the index map
    out_ref[...] = jnp.where(valid, row, jnp.zeros_like(row))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def moe_dispatch_gather(x, slot_tok, *, block_d: int = 128,
                        interpret: bool):
    """out[s] = x[slot_tok[s]] (zero row for padded slots)."""
    t, d = x.shape
    (s,) = slot_tok.shape
    assert d % block_d == 0, (d, block_d)
    grid = (s, d // block_d)

    return pl.pallas_call(
        functools.partial(_kernel, n_tokens=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # clamp pad indices (== T) for the DMA only; the kernel
                # masks the payload using the unclamped prefetch value
                pl.BlockSpec((1, block_d),
                             lambda i, j, tok: (jnp.minimum(tok[i], t - 1), j)),
            ],
            out_specs=pl.BlockSpec((1, block_d), lambda i, j, tok: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((s, d), x.dtype),
        interpret=interpret,
    )(slot_tok.astype(jnp.int32), x)

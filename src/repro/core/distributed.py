"""Distributed semiring SpMV/SpMSpV over a device mesh (paper §4.1.1 + §6.3).

The paper's four-phase accounting survives intact, but UPMEM's host-mediated
transfers become on-fabric collectives:

    Load     : all-gather of the input vector onto the devices that need it
    Kernel   : local semiring SpMV / SpMSpV (shard_map body)
    Retrieve : moving partial outputs — here an all-to-all (⊕-reduce-scatter)
    Merge    : the ⊕-reduction itself (psum / pmin / pmax in the semiring)

Strategies (paper Fig. 3):
    row   — A row-sharded over the full flat axis; Load = all-gather(x);
            output lands sharded; no Retrieve/Merge.
    col   — A col-sharded; no Load; Kernel emits full-length partials;
            Retrieve+Merge = ⊕-reduce-scatter over the flat axis.
    2d    — A tiled over (axis_r, axis_c); Load = all-gather(x) over axis_r
            (x is sharded over axis_c, replicated over axis_r after gather);
            Retrieve+Merge = ⊕-reduce-scatter over axis_c.

The *shape* of that ⊕-reduce-scatter is itself a free choice — the paper's
"direct interconnection networks among PIM cores" recommendation. Every
factory takes ``topology`` (one of :data:`repro.core.collectives
.MERGE_FAMILIES`: ``flat`` / ``ring`` / ``tree`` / ``staged2d``) and routes
the Merge through :func:`repro.core.collectives.merge`; all topologies
produce the identical output layout (and bit-identical results on
order-exact data), differing only in modeled bytes-on-wire and step count
(priced by graphs.cost_model.merge_wire_cost, picked by
``strategy="auto"``).

Between traversal iterations, ``vec_to_2d_layout`` converts the output
layout into the next iteration's input layout — the paper's inter-iteration
retrieve+reload through the host CPU, which on TPU is a collective permute.

Which rows/cols land on which device is the :class:`~repro.core.partition
.PartitionPlan`'s decision (``balance="rows"`` equal-count tiles vs
``balance="nnz"`` work-balanced bands): every factory here consumes the
plan through the PartitionedMatrix and assumes its canonical vector
layouts — input chunk ``g = c*R + r`` holds piece *r* of column band *c*,
output chunk ``g = r*C + c`` holds piece *c* of row band *r* (identical to
plain row-major slicing for ``balance="rows"``).  Callers shard/unshard
through the plan helpers (``plan.shard_input_vector`` etc.); the
collectives themselves are balance-agnostic.  The cost-model planner
(graphs.cost_model.choose_partition) picks strategy+balance per graph.

This module is the **single definition point** for the four-phase
vocabulary above; other modules (core.pipeline, serve.graph_engine, the
benchmarks) cross-reference it instead of re-explaining the phases.
``build_phase_fns`` exposes each phase as its own jitted closure. The
closures are *non-blocking by construction* (JAX dispatch is async): the
caller chooses the schedule. ``benchmarks.phases`` times them with a hard
sync after every phase — the paper's blocking-DMA schedule — while
``core.pipeline.iterate_phases`` dispatches them back-to-back so
Retrieve+Merge of iteration *t* overlaps the Load of *t+1*, the paper's
proposed non-blocking fix.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.collectives import merge as merge_collective
from repro.core.collectives import merge_chunks, plan_merge
from repro.obs import trace
from repro.core.partition import PartitionedMatrix
from repro.core.semiring import Semiring
from repro.core.spgemm import apply_mask, spgemm_masked
from repro.core.spmspv import Frontier, frontier_from_dense
from repro.core.spmspv import spmspv as _spmspv
from repro.core.spmv import spmv as _spmv

Array = jax.Array


def _merge_plans(mesh: Mesh, axis_names: Sequence[str], topology: str,
                 merge_order: str):
    """(col_plan, col2d_plan) for this mesh — the MergePlans the col and 2d
    strategies' Retrieve+Merge route through (collectives.plan_merge)."""
    ar, ac = axis_names
    shape = (mesh.shape[ar], mesh.shape[ac])
    return (plan_merge("col", shape, topology, axis_names, merge_order),
            plan_merge("2d", shape, topology, axis_names, merge_order))


def _local_matvec(a_local, x_full: Array, sr: Semiring, kernel: str, impl: str) -> Array:
    if kernel == "spmv":
        return _spmv(a_local, x_full, sr, impl=impl)
    f = frontier_from_dense(x_full, sr)
    return _spmspv(a_local, f, sr, impl=impl)


def _check_fused(pm: PartitionedMatrix) -> None:
    if pm.fmt != "bsr":
        raise ValueError(
            f"fused=True streams ELL-of-tiles shards and needs fmt='bsr'; "
            f"this partition holds fmt={pm.fmt!r}")


def _fused_partials(a_local, x_full: Array, sr: Semiring, kernel: str,
                    d: int):
    """Fused Load+Kernel partials for a merge over ``d`` chunks.  When the
    block-row count divides evenly the kernel scatters its output
    chunk-major (the fused Retrieve epilogue) for merge_chunks; otherwise
    it emits the flat layout and the merge reshapes as before — either
    way the tile streaming itself is double-buffered.  Returns
    (partials, chunked?)."""
    from repro.kernels import ops  # deferred: kernels import pallas

    mb = a_local.tiles.shape[0]
    chunks = d if mb % d == 0 else None
    if kernel == "spmv":
        y = ops.semiring_spmv_fused(a_local, x_full, sr, chunks=chunks)
    else:
        f = frontier_from_dense(x_full, sr)
        y = ops.semiring_spmspv_fused(a_local, f, sr, chunks=chunks)
    return y, chunks is not None


def gather_frontier(x_local: Array, sr: Semiring, f_local: int,
                    axis_name) -> Frontier:
    """The paper's compressed Load phase: each shard compresses its slice of
    the input vector to a (indices, values) frontier of capacity ``f_local``
    and only THAT crosses the fabric — Load wire bytes drop from n_per to
    2*f_local per peer, the SpMSpV load saving of §4.1/§6.2.

    Capacity contract: a shard holding more than ``f_local`` nonzeros
    truncates (callers size f_local from the density bound, exactly like the
    paper sizes its DPU transfer buffers)."""
    n_per = x_local.shape[0]
    f = frontier_from_dense(x_local, sr, f_max=f_local)
    idx_g = jax.lax.all_gather(f.indices, axis_name)     # [D, f] on the wire
    val_g = jax.lax.all_gather(f.values, axis_name)
    d = idx_g.shape[0]
    offs = (jnp.arange(d, dtype=jnp.int32) * n_per)[:, None]
    ok = idx_g < n_per                                   # pad index = n_per
    gidx = jnp.where(ok, idx_g + offs, d * n_per).astype(jnp.int32)
    return Frontier(gidx.reshape(-1), val_g.reshape(-1).astype(sr.dtype),
                    jnp.sum(ok.astype(jnp.int32)), d * n_per)


def _check_plan(pm: PartitionedMatrix, strategy: str) -> None:
    """A strategy only makes sense on a matching grid: the plan's split
    axes must line up with the collectives the strategy issues."""
    r_parts, c_parts = pm.grid
    if strategy == "row" and c_parts != 1:
        raise ValueError(f"row strategy needs a (D, 1) grid, got {pm.grid}")
    if strategy == "col" and r_parts != 1:
        raise ValueError(f"col strategy needs a (1, D) grid, got {pm.grid}")


def make_distributed_matvec(
    mesh: Mesh,
    pm: PartitionedMatrix,
    sr: Semiring,
    strategy: str,
    kernel: str = "spmv",
    impl: str = "auto",
    axis_names: Sequence[str] = ("dr", "dc"),
    f_local: int | None = None,
    topology: str = "flat",
    merge_order: str = "rc",
    fused: bool = False,
) -> Callable[[object, Array], Array]:
    """Build `fn(parts, x_sharded) -> y_sharded` under shard_map.

    x/y layout is the canonical flat one: [D, n_per] sharded over the flat
    device axes (the plan's input/output layouts — see
    ``PartitionPlan.shard_input_vector`` / ``unshard_output_vector``; for
    ``balance="rows"`` these are plain row-major chunks, so iterative
    algorithms can feed y straight back in after the 2d reshard).  With
    ``balance="nnz"`` the input and output chunkings differ, so chaining
    iterations requires an unshard/reshard through the plan between steps.

    ``f_local`` (SpMSpV only) switches the Load phase to the paper's
    compressed form: each shard all-gathers a capacity-``f_local`` frontier
    instead of its dense slice (see gather_frontier).

    ``topology`` picks the Merge collective family (core.collectives;
    ``merge_order`` is the staged2d stage order). Output layout and — on
    order-exact data — bits are identical across topologies; the row
    strategy has no Merge, so the choice is a no-op there.

    ``fused=True`` (fmt="bsr" only) swaps the local compute for the
    double-buffered streaming kernels (kernels/ops.semiring_spmv_fused /
    _spmspv_fused): adjacency tiles stay in ANY/HBM and only real /
    frontier-active slots cross into VMEM, prefetched one tile ahead;
    where the block grid allows, the kernel also scatters its partials
    chunk-major so the Merge starts from the kernel's own output
    (collectives.merge_chunks).  Bit-identical to ``fused=False``.
    """
    _check_plan(pm, strategy)
    if fused:
        _check_fused(pm)
    ar, ac = axis_names
    flat = (ar, ac)
    r_parts, c_parts = pm.grid
    d = pm.n_devices
    col_mp, col2d_mp = _merge_plans(mesh, axis_names, topology, merge_order)
    compressed = f_local is not None and kernel == "spmspv"

    a_specs = jax.tree.map(lambda _: P(flat), pm.parts)

    def strip_lead(a_tree):
        return jax.tree.map(lambda x: x[0], a_tree)

    loc_impl = "fused" if fused else impl

    if strategy == "row":
        def body(parts, x):
            a_local = strip_lead(parts)
            if compressed:
                f = gather_frontier(x[0], sr, f_local, flat)       # Load
                y = _spmspv(a_local, f, sr, impl=loc_impl)         # Kernel
            else:
                x_full = jax.lax.all_gather(x, flat, tiled=True).reshape(-1)
                y = _local_matvec(a_local, x_full, sr, kernel, loc_impl)
            return y[None]  # already row-sharded; no Retrieve/Merge

        in_specs = (a_specs, P(flat))
        out_specs = P(flat)

    elif strategy == "col":
        def body(parts, x):
            a_local = strip_lead(parts)
            if fused:
                y_partial, chunked = _fused_partials(a_local, x[0], sr,
                                                     kernel, d)
                y = (merge_chunks(y_partial, sr, col_mp) if chunked
                     else merge_collective(y_partial, sr, col_mp))
            else:
                y_partial = _local_matvec(a_local, x[0], sr, kernel, impl)
                y = merge_collective(y_partial, sr, col_mp)  # Retrieve+Merge
            return y[None]

        in_specs = (a_specs, P(flat))
        out_specs = P(flat)

    elif strategy == "2d":
        # Grid must match the two mesh axes.
        assert (r_parts, c_parts) == (mesh.shape[ar], mesh.shape[ac]), (
            f"2d grid {pm.grid} != mesh {(mesh.shape[ar], mesh.shape[ac])}")

        def body(parts, x):
            a_local = strip_lead(strip_lead(parts))
            # Load: gather x chunks across axis_r. With the column-major 2d
            # input layout (x2[r, c] = global chunk c*R + r), the gather over
            # ar assembles exactly column block c on every grid row.
            if compressed:
                f = gather_frontier(x[0, 0], sr, f_local, ar)
                y_partial = _spmspv(a_local, f, sr, impl=loc_impl)
            elif fused:
                x_cols = jax.lax.all_gather(x[0, 0], ar, tiled=True).reshape(-1)
                y_partial, chunked = _fused_partials(a_local, x_cols, sr,
                                                     kernel, c_parts)
                if chunked:
                    return merge_chunks(y_partial, sr, col2d_mp)[None, None]
            else:
                x_cols = jax.lax.all_gather(x[0, 0], ar, tiled=True).reshape(-1)
                y_partial = _local_matvec(a_local, x_cols, sr, kernel, impl)
            # Retrieve+Merge over the column axis → y2[r, c] = chunk r*C + c.
            y = merge_collective(y_partial, sr, col2d_mp)
            return y[None, None]

        in_specs = (jax.tree.map(lambda _: P((ar,), (ac,)), pm.parts), P(ar, ac))
        out_specs = P(ar, ac)

        fn_body = shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)

        def fn2d(parts, x):
            reshaped = jax.tree.map(
                lambda v: v.reshape((r_parts, c_parts) + v.shape[1:]), parts)
            x2 = vec_to_2d_layout(x, pm.grid)
            y2 = fn_body(reshaped, x2)
            return y2.reshape(d, -1)  # row-major chunks (canonical layout)

        return fn2d
    else:
        raise ValueError(strategy)

    return shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def make_distributed_spmv(mesh: Mesh, pm: PartitionedMatrix, sr: Semiring,
                          strategy: str, **kwargs
                          ) -> Callable[[object, Array], Array]:
    """make_distributed_matvec pinned to the dense-input SpMV kernel."""
    return make_distributed_matvec(mesh, pm, sr, strategy, kernel="spmv",
                                   **kwargs)


def make_distributed_spmspv(mesh: Mesh, pm: PartitionedMatrix, sr: Semiring,
                            strategy: str, **kwargs
                            ) -> Callable[[object, Array], Array]:
    """make_distributed_matvec pinned to the sparse-frontier SpMSpV kernel."""
    return make_distributed_matvec(mesh, pm, sr, strategy, kernel="spmspv",
                                   **kwargs)


def make_distributed_batched_matvec(
    mesh: Mesh,
    pm: PartitionedMatrix,
    sr: Semiring,
    strategy: str,
    kernel: str = "spmv",
    impl: str = "auto",
    axis_names: Sequence[str] = ("dr", "dc"),
    topology: str = "flat",
    merge_order: str = "rc",
) -> Callable[[object, Array], Array]:
    """[B, n]-block counterpart of make_distributed_matvec: the adjacency
    shards exactly as in the unbatched path (paper Fig. 3 strategies) while
    every Load/Retrieve/Merge collective carries the whole query block —
    B traversals amortize one partitioning's collective schedule.

    x/y layout: [D, B, n_per] sharded over the flat device axes (the
    canonical flat layout with a batch dim inserted after the device axis).
    The compressed-frontier Load (``f_local``) stays single-query only:
    per-row frontiers have different live counts, so a shared capacity
    would re-introduce the truncation ambiguity the ladder avoids.
    Balanced (``balance="nnz"``) plans work unchanged: shard the block with
    ``plan.shard_input_batch`` and recover it with ``unshard_output_batch``.
    ``topology``/``merge_order`` pick the Merge collective exactly as in
    make_distributed_matvec (the whole [B, ·] block rides each exchange).
    """
    _check_plan(pm, strategy)
    ar, ac = axis_names
    flat = (ar, ac)
    r_parts, c_parts = pm.grid
    d = pm.n_devices
    col_mp, col2d_mp = _merge_plans(mesh, axis_names, topology, merge_order)

    a_specs = jax.tree.map(lambda _: P(flat), pm.parts)

    def strip_lead(a_tree):
        return jax.tree.map(lambda x: x[0], a_tree)

    def local_batch_matvec(a_local, xs_full: Array) -> Array:
        return jax.vmap(
            lambda x: _local_matvec(a_local, x, sr, kernel, impl))(xs_full)

    if strategy == "row":
        def body(parts, x):
            a_local = strip_lead(parts)
            x_full = jax.lax.all_gather(x[0], flat, tiled=True, axis=1)
            y = local_batch_matvec(a_local, x_full)     # [B, m_local]
            return y[None]

        return shard_map(body, mesh=mesh, in_specs=(a_specs, P(flat)),
                         out_specs=P(flat), check_vma=False)

    if strategy == "col":
        def body(parts, x):
            a_local = strip_lead(parts)
            y_partial = local_batch_matvec(a_local, x[0])   # [B, m_full]
            y = merge_collective(y_partial, sr, col_mp, axis=1)
            return y[None]

        return shard_map(body, mesh=mesh, in_specs=(a_specs, P(flat)),
                         out_specs=P(flat), check_vma=False)

    if strategy == "2d":
        assert (r_parts, c_parts) == (mesh.shape[ar], mesh.shape[ac]), (
            f"2d grid {pm.grid} != mesh {(mesh.shape[ar], mesh.shape[ac])}")

        def body(parts, x):
            a_local = strip_lead(strip_lead(parts))
            x_cols = jax.lax.all_gather(x[0, 0], ar, tiled=True, axis=1)
            y_partial = local_batch_matvec(a_local, x_cols)
            y = merge_collective(y_partial, sr, col2d_mp, axis=1)
            return y[None, None]

        fn_body = shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P((ar,), (ac,)), pm.parts),
                      P(ar, ac)),
            out_specs=P(ar, ac), check_vma=False)

        def fn2d(parts, x):
            reshaped = jax.tree.map(
                lambda v: v.reshape((r_parts, c_parts) + v.shape[1:]), parts)
            x2 = x.reshape(c_parts, r_parts, *x.shape[1:]).transpose(1, 0, 2, 3)
            y2 = fn_body(reshaped, x2)
            return y2.reshape(d, x.shape[1], -1)

        return fn2d

    raise ValueError(strategy)


def make_distributed_spgemm(
    mesh: Mesh,
    pm: PartitionedMatrix,
    sr: Semiring,
    strategy: str,
    axis_names: Sequence[str] = ("dr", "dc"),
    topology: str = "flat",
    merge_order: str = "rc",
) -> Callable[..., Array]:
    """Partitioned masked SpGEMM C = (A ⊕.⊗ B) ⊙ M over the Fig.-3
    strategies — the matrix-matrix counterpart of make_distributed_matvec.
    The four-phase accounting carries over with B's *rows* playing the
    input-vector role (they index A's columns):

        row — A row-sharded; Load = all-gather(B rows); C lands
              row-sharded; no Retrieve/Merge.
        col — A col-sharded; B rows stay sharded (no Load); each device
              emits a full-height partial C; Retrieve+Merge =
              ⊕-reduce-scatter of C row blocks over the flat axis.
        2d  — A tiled (R, C); Load = all-gather(B row chunks) over axis_r;
              Retrieve+Merge = ⊕-reduce-scatter of C rows over axis_c.

    Returns ``fn(parts, b_sharded, mask_sharded=None) -> c_sharded``. B is
    [D, k_per, N] and C / mask are [D, m_per, N] in the canonical flat
    layout. The mask is structural (see core.spgemm) and is applied
    post-merge, on already-sharded output rows — masking never crosses
    the fabric.  B rows shard via ``plan.shard_input_rows``; C and the mask
    live in the output-row layout (``plan.shard_output_rows`` /
    ``unshard_output_rows``), so balanced plans work unchanged.
    ``topology``/``merge_order`` pick the Merge collective for C's row
    blocks exactly as in make_distributed_matvec."""
    _check_plan(pm, strategy)
    ar, ac = axis_names
    flat = (ar, ac)
    r_parts, c_parts = pm.grid
    d = pm.n_devices
    col_mp, col2d_mp = _merge_plans(mesh, axis_names, topology, merge_order)

    a_specs = jax.tree.map(lambda _: P(flat), pm.parts)

    def strip_lead(a_tree):
        return jax.tree.map(lambda x: x[0], a_tree)

    def local_spgemm(a_local, b_full: Array) -> Array:
        return spgemm_masked(a_local, b_full, sr)

    if strategy == "row":
        def body(parts, b, mask):
            a_local = strip_lead(parts)
            b_full = jax.lax.all_gather(b[0], flat, tiled=True, axis=0)
            c = local_spgemm(a_local, b_full)           # Kernel
            c = apply_mask(c, mask[0], sr)
            return c[None]  # already row-sharded; no Retrieve/Merge

        in_specs = (a_specs, P(flat), P(flat))
        out_specs = P(flat)

    elif strategy == "col":
        def body(parts, b, mask):
            a_local = strip_lead(parts)
            c_partial = local_spgemm(a_local, b[0])     # Kernel (no Load)
            c = merge_collective(c_partial, sr, col_mp)
            return apply_mask(c, mask[0], sr)[None]

        in_specs = (a_specs, P(flat), P(flat))
        out_specs = P(flat)

    elif strategy == "2d":
        assert (r_parts, c_parts) == (mesh.shape[ar], mesh.shape[ac]), (
            f"2d grid {pm.grid} != mesh {(mesh.shape[ar], mesh.shape[ac])}")

        def body(parts, b, mask):
            a_local = strip_lead(strip_lead(parts))
            # Load: assemble column block c's B rows across axis_r (B rows
            # use the same column-major 2d input layout as the matvec x).
            b_cols = jax.lax.all_gather(b[0, 0], ar, tiled=True, axis=0)
            c_partial = local_spgemm(a_local, b_cols)
            c = merge_collective(c_partial, sr, col2d_mp)
            return apply_mask(c, mask[0, 0], sr)[None, None]

        fn_body = shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P((ar,), (ac,)), pm.parts),
                      P(ar, ac), P(ar, ac)),
            out_specs=P(ar, ac), check_vma=False)

        def fn2d(parts, b, mask=None):
            if mask is None:
                mask = jnp.full((d, pm.shape[0] // d, b.shape[2]), sr.one,
                                sr.dtype)
            reshaped = jax.tree.map(
                lambda v: v.reshape((r_parts, c_parts) + v.shape[1:]), parts)
            # B rows: canonical chunk g → 2d input layout [r, c] = c*R + r.
            b2 = b.reshape(c_parts, r_parts, *b.shape[1:]).transpose(1, 0, 2, 3)
            # Output rows land as y2[r, c] = chunk r*C + c (row-major).
            m2 = mask.reshape(r_parts, c_parts, *mask.shape[1:])
            c2 = fn_body(reshaped, b2, m2)
            return c2.reshape(d, *c2.shape[2:])

        return fn2d
    else:
        raise ValueError(strategy)

    fn_body = shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)

    def fn(parts, b, mask=None):
        if mask is None:
            m_per = pm.shape[0] // d
            mask = jnp.full((d, m_per, b.shape[2]), sr.one, sr.dtype)
        return fn_body(parts, b, mask)

    return fn


def _traced_phase(fn, name: str, attrs: dict):
    """Wrap one phase closure for observability (repro.obs.trace).

    Tracing disabled (the default): one module-global None check, then
    straight through to the jitted closure — async dispatch untouched.
    Tracing enabled: the call runs inside a span and blocks until ready
    *inside* it, so the span measures the phase's device time — the
    paper's blocking-DMA accounting (benchmarks.phases' schedule), which
    is what makes per-phase span sums comparable to wall time and to
    graphs.cost_model predictions. The extra sync moves host timing only;
    values are bit-identical either way."""
    if fn is None:
        return None

    def run(*args):
        t = trace.active()
        if t is None:
            return fn(*args)
        with t.span(name, **attrs):
            return jax.block_until_ready(fn(*args))
    return run


def build_phase_fns(mesh: Mesh, pm: PartitionedMatrix, sr: Semiring,
                    strategy: str, kernel: str, f_local: int | None = None,
                    donate: bool = False, topology: str = "flat",
                    merge_order: str = "rc", fused: bool = False):
    """Per-phase jitted closures for one Fig.-3 strategy (see the module
    docstring for the phase vocabulary). Returns a dict:

        load           : (parts, xs) -> gathered input   (None: no Load)
        kernel         : (parts, xs, xf) -> partials     (None: only fused)
        retrieve_merge : (parts, ys) -> merged output    (None: no R+M)
        feedback       : ys -> xs-layout output          (None: identity)
        e2e            : (parts, xs) -> output, the production
                         make_distributed_matvec path in one program

    Every closure dispatches asynchronously; schedule (blocking vs
    pipelined) is the caller's choice — see core.pipeline. ``feedback``
    converts the Retrieve+Merge output back into the canonical input
    layout so iterative algorithms can chain calls (only the 2d strategy
    needs a real permute). ``f_local`` switches SpMSpV to the paper's
    compressed Load (the frontier crosses the fabric instead of the dense
    vector; see gather_frontier). ``donate=True`` additionally donates the
    Retrieve+Merge input buffer — the kernel's partials are consumed
    exactly once, so the merge may reuse them in place (the paper's DMA
    double-buffer); ignored on backends without donation support (CPU).
    With donation enabled, never call ``retrieve_merge`` twice on the same
    partials (repeated timing does exactly that — benchmarks.phases times
    undonated closures for this reason).

    Balanced (``balance="nnz"``) plans time/apply every phase correctly;
    only the inter-iteration chaining (``feedback`` + re-Load) additionally
    assumes the input and output chunkings coincide, which holds for
    ``balance="rows"`` square tiles — iterating a balanced plan requires a
    plan unshard/reshard between steps instead.

    ``topology``/``merge_order`` pick the Merge collective family
    (core.collectives) for the ``retrieve_merge`` closure and the fused
    ``e2e`` program alike; the per-phase split — and with it the pipeline
    overlap in core.pipeline — is unchanged, since every topology is one
    jittable closure with the same in/out layout.

    ``fused=True`` (fmt="bsr" only) restructures the phase dict around the
    double-buffered streaming kernels: the tile Load happens *inside* the
    kernel (ANY/HBM → two-slot VMEM window, one tile ahead), and for the
    col/2d strategies the Kernel and Retrieve+Merge run as ONE jitted
    program — the kernel scatters chunk-major partials that
    collectives.merge_chunks consumes directly, so no flat partial ever
    materialises between separate phase programs. Consequently
    ``retrieve_merge`` is None and the ``kernel`` closure returns
    already-merged output; run_phases_once / iterate_phases handle that
    shape unchanged, and the unfused dict (``fused=False``) is the
    bit-identity oracle (asserted in tests/test_distributed.py).
    """
    _check_plan(pm, strategy)
    if fused:
        _check_fused(pm)
    ar, ac = "dr", "dc"
    flat = (ar, ac)
    d = pm.n_devices
    col_mp, col2d_mp = _merge_plans(mesh, (ar, ac), topology, merge_order)
    a_specs = jax.tree.map(lambda _: P(flat), pm.parts)
    strip = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
    rm_jit_kwargs = {}
    if donate and jax.default_backend() in ("gpu", "tpu"):
        rm_jit_kwargs["donate_argnums"] = (1,)
    fns = {"feedback": None}

    loc_impl = "fused" if fused else "auto"

    if strategy == "row":
        load = shard_map(
            lambda x: jax.lax.all_gather(x, flat, tiled=True).reshape(-1)[None],
            mesh=mesh, in_specs=P(flat), out_specs=P(flat), check_vma=False)

        def kern(parts, x_full):
            return _local_matvec(strip(parts), x_full[0], sr, kernel,
                                 loc_impl)[None]

        kern_sm = shard_map(kern, mesh=mesh, in_specs=(a_specs, P(flat)),
                            out_specs=P(flat), check_vma=False)
        fns["load"] = jax.jit(lambda parts, xs: load(xs))
        fns["kernel"] = jax.jit(
            lambda parts, xs, xf: kern_sm(parts, xf))
        fns["retrieve_merge"] = None        # row-wise: output stays sharded

    elif strategy == "col":
        if fused:
            # Kernel + Retrieve + Merge as one program: the streaming
            # kernel scatters chunk-major partials, merge_chunks folds
            # them — no flat partial between phase programs.
            def kern_f(parts, x):
                y_partial, chunked = _fused_partials(strip(parts), x[0], sr,
                                                     kernel, d)
                y = (merge_chunks(y_partial, sr, col_mp) if chunked
                     else merge_collective(y_partial, sr, col_mp))
                return y[None]

            km_sm = shard_map(kern_f, mesh=mesh, in_specs=(a_specs, P(flat)),
                              out_specs=P(flat), check_vma=False)
            fns["load"] = None
            fns["kernel"] = jax.jit(lambda parts, xs, _xf: km_sm(parts, xs))
            fns["retrieve_merge"] = None    # folded into the kernel program
        else:
            def kern(parts, x):
                return _local_matvec(strip(parts), x[0], sr, kernel,
                                     "auto")[None]

            kern_sm = shard_map(kern, mesh=mesh, in_specs=(a_specs, P(flat)),
                                out_specs=P(flat), check_vma=False)
            rm = shard_map(
                lambda y: merge_collective(y[0], sr, col_mp)[None],
                mesh=mesh, in_specs=P(flat), out_specs=P(flat),
                check_vma=False)
            fns["load"] = None              # input already sharded
            fns["kernel"] = jax.jit(lambda parts, xs, _xf: kern_sm(parts, xs))
            fns["retrieve_merge"] = jax.jit(lambda parts, ys: rm(ys),
                                            **rm_jit_kwargs)

    elif strategy == "2d":
        r_parts, c_parts = pm.grid
        reshape_parts = lambda parts: jax.tree.map(  # noqa: E731
            lambda v: v.reshape((r_parts, c_parts) + v.shape[1:]), parts)
        a2 = jax.tree.map(lambda _: P((ar,), (ac,)), pm.parts)

        load = shard_map(
            lambda x: jax.lax.all_gather(x[0, 0], ar, tiled=True)[None, None],
            mesh=mesh, in_specs=P(ar, ac), out_specs=P(ar, ac), check_vma=False)

        if fused:
            def kern_f(parts, xc):
                a_local = strip(strip(parts))
                y_partial, chunked = _fused_partials(a_local, xc[0, 0], sr,
                                                     kernel, c_parts)
                y = (merge_chunks(y_partial, sr, col2d_mp) if chunked
                     else merge_collective(y_partial, sr, col2d_mp))
                return y[None, None]

            km_sm = shard_map(kern_f, mesh=mesh, in_specs=(a2, P(ar, ac)),
                              out_specs=P(ar, ac), check_vma=False)
            fns["load"] = jax.jit(
                lambda parts, xs: load(vec_to_2d_layout(xs, pm.grid)))
            fns["kernel"] = jax.jit(
                lambda parts, xs, xf: km_sm(reshape_parts(parts), xf))
            fns["retrieve_merge"] = None    # folded into the kernel program
        else:
            def kern(parts, xc):
                a_local = strip(strip(parts))
                return _local_matvec(a_local, xc[0, 0], sr, kernel,
                                     "auto")[None, None]

            kern_sm = shard_map(kern, mesh=mesh, in_specs=(a2, P(ar, ac)),
                                out_specs=P(ar, ac), check_vma=False)
            rm = shard_map(
                lambda y: merge_collective(y[0, 0], sr, col2d_mp)[None, None],
                mesh=mesh, in_specs=P(ar, ac), out_specs=P(ar, ac),
                check_vma=False)

            fns["load"] = jax.jit(
                lambda parts, xs: load(vec_to_2d_layout(xs, pm.grid)))
            fns["kernel"] = jax.jit(
                lambda parts, xs, xf: kern_sm(reshape_parts(parts), xf))
            fns["retrieve_merge"] = jax.jit(lambda parts, ys: rm(ys),
                                            **rm_jit_kwargs)
        # R+M lands chunks row-major ([r, c] = chunk r*C + c); flattening
        # restores the canonical layout the Load expects next iteration.
        fns["feedback"] = jax.jit(lambda ys: ys.reshape(d, -1))
    else:
        raise ValueError(strategy)

    fns["e2e"] = jax.jit(make_distributed_matvec(mesh, pm, sr, strategy,
                                                 kernel=kernel,
                                                 f_local=f_local,
                                                 topology=topology,
                                                 merge_order=merge_order,
                                                 fused=fused))
    if f_local is not None and strategy in ("row", "2d"):
        # compressed Load: time the per-shard compress + frontier gather
        axis = flat if strategy == "row" else ar

        def c_load(x):
            f = gather_frontier(x[0] if strategy == "row" else x[0, 0],
                                sr, f_local, axis)
            lead = ((None,) if strategy == "row" else (None, None))
            idx = f.indices[lead]
            val = f.values[lead]
            return idx, val

        spec = P(flat) if strategy == "row" else P(ar, ac)

        def pre(xs):
            return xs if strategy == "row" else vec_to_2d_layout(xs, pm.grid)

        loader = shard_map(c_load, mesh=mesh, in_specs=spec,
                           out_specs=(spec, spec), check_vma=False)
        fns["load"] = jax.jit(lambda parts, xs: loader(pre(xs)))
        fns["kernel"] = None          # folded into e2e - load (derived)

    # Observability wrap (repro.obs.trace): every returned closure is a
    # _traced_phase — pass-through when no tracer is installed, a
    # blocking span named phase/<name> otherwise. Span attrs carry the
    # wire accounting inline (core must not import graphs.cost_model):
    # Load bytes are the elements each device assembles, Merge bytes and
    # steps come from the MergePlan's own schedule description.
    m_pad, n_pad = pm.shape
    r_parts, c_parts = pm.grid
    elem = jnp.dtype(sr.dtype).itemsize
    load_elems = {"row": n_pad, "col": 0, "2d": n_pad // c_parts}[strategy]
    if f_local is not None and strategy in ("row", "2d"):
        # compressed Load: f_local (index, value) pairs per axis peer
        load_elems = 2 * f_local * (d if strategy == "row" else r_parts)
    mp = col_mp if strategy == "col" else col2d_mp
    m_merge = {"row": 0, "col": m_pad, "2d": m_pad // r_parts}[strategy]
    wire = mp.wire_elements(m_merge) if strategy != "row" else 0.0
    steps = mp.n_steps if strategy != "row" else 0
    base = {"strategy": strategy, "kernel": kernel, "topology": topology,
            "devices": d, "fused": fused}
    attrs = {
        "load": {**base, "phase": "load", "bytes": load_elems * elem},
        "kernel": {**base, "phase": "kernel"},
        "retrieve_merge": {**base, "phase": "retrieve_merge",
                           "bytes": wire * elem, "steps": steps},
        "feedback": {**base, "phase": "feedback"},
        "e2e": {**base, "phase": "e2e",
                "bytes": (load_elems + wire) * elem},
    }
    for name in ("load", "kernel", "retrieve_merge", "feedback", "e2e"):
        fns[name] = _traced_phase(fns[name], f"phase/{name}", attrs[name])
    return fns


def vec_to_2d_layout(x: Array, grid) -> Array:
    """Canonical [D, n_per] (chunk g at row g) → 2d input layout
    x2[r, c] = chunk (c*R + r). Under pjit this is a collective permute —
    the paper's inter-iteration vector reload through the host CPU."""
    r_parts, c_parts = grid
    # x2[r, c] = x[c*R + r]: reshape to (C, R) chunk grid then transpose.
    return x.reshape(c_parts, r_parts, -1).transpose(1, 0, 2)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))

"""Batched multi-source traversals: BFS/SSSP/PPR over a [B, n] frontier
block (the paper's §4 linear-algebra iteration, lifted to the many-query
regime the ROADMAP serves).

One ``lax.while_loop`` advances all B queries in lockstep; per-query
adaptive SpMSpV↔SpMV switching happens as data flow (see
core.adaptive.adaptive_matvec_batch), and a query that converges is frozen
— its state rows stop updating and its trace stops recording — so every
row of the batched result is element-equal to the corresponding
single-source run (asserted in tests/test_multi_query.py, including the
kernel-choice trace and per-query iteration counts).

``mesh``/``axis_name`` shard the [B, n] block over devices: queries are
independent, so the block row-shards with no cross-device traffic beyond
the scalar convergence reduction.

Each runner's loop body runs under a name scope (``bfs_step``,
``sssp_step``, ``ppr_step``), as do the matvecs beneath it (core.spmv,
core.spmspv, their ⊕-reductions under ``segment_reduce``), so a device
trace names the ops of one iteration by what they do.

Every runner takes the engine's matrices as jit arguments
(:class:`BatchRunner`): the compiled program's size does not grow with the
graph, and one program serves any graph of the same shapes.

``traverse_multi_buckets`` is the pipelined bucket mode: several source
buckets drain through core.pipeline.pipeline_buckets so bucket *t+1*'s
jitted while_loop is dispatched while bucket *t*'s results are awaited —
the serving layer's phase overlap (see serve.graph_engine).
"""
from __future__ import annotations

import threading
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.adaptive import select_kernel_batch
from repro.core.pipeline import pipeline_buckets
from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, PLUS_TIMES
from repro.graphs.engine import GraphEngine, density_of_batch

Array = jax.Array


class BFSBatchResult(NamedTuple):
    levels: Array       # int32 [B, n_true]; -1 = unreached
    iterations: Array   # int32 [B]
    densities: Array    # f32 [B, max_iters]
    kernel_used: Array  # int32 [B, max_iters]; 0 = SpMSpV, 1 = SpMV, -1 unused


class SSSPBatchResult(NamedTuple):
    dist: Array         # f32 [B, n_true]; +inf = unreachable
    iterations: Array
    densities: Array
    kernel_used: Array


class PPRBatchResult(NamedTuple):
    rank: Array         # f32 [B, n_true]
    iterations: Array
    densities: Array
    kernel_used: Array
    residual: Array     # f32 [B]


def _kernel_codes(policy: str, densities: Array, threshold: float) -> Array:
    """Per-query kernel trace codes, matching the single-source recording."""
    if policy == "spmv":
        return jnp.ones(densities.shape, jnp.int32)
    if policy == "spmspv":
        return jnp.zeros(densities.shape, jnp.int32)
    return select_kernel_batch(densities, threshold)


def _constrain_block(x: Array, mesh: Mesh | None, axis_name: str) -> Array:
    """Row-shard a [B, ...] block over ``axis_name`` when a mesh is given."""
    if mesh is None:
        return x
    spec = P(axis_name, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _masked_trace_update(trace: Array, it: Array, active: Array,
                         value: Array) -> Array:
    """trace[:, it] = value where the query is still active."""
    return trace.at[:, it].set(jnp.where(active, value, trace[:, it]))


class BatchRunner:
    """A batched traversal compiled with the graph as an argument.

    ``jitted(mats, *args)`` is the jitted program: ``body(engine, *args)``
    traced against ``engine.bind(mats)``, so the engine's matrices enter as
    parameters, never as constants. Calling the runner feeds it the
    engine's own matrices — replicated over ``mesh`` once, here, when the
    query block is sharded."""

    def __init__(self, engine: GraphEngine, body: Callable,
                 mesh: Mesh | None = None):
        self.mats = engine.mats
        if mesh is not None:
            self.mats = jax.device_put(self.mats, NamedSharding(mesh, P()))
        self.jitted = jax.jit(
            lambda mats, *args: body(engine.bind(mats), *args))

    def __call__(self, *args):
        return self.jitted(self.mats, *args)


def make_bfs_multi(engine: GraphEngine, batch: int, max_iters: int = 64,
                   policy: str = "adaptive", mesh: Mesh | None = None,
                   axis_name: str = "batch"
                   ) -> Callable[[Array], BFSBatchResult]:
    """Build a jitted runner: sources [B] int32 -> BFSBatchResult."""
    sr = engine.sr
    assert sr.name == BOOL_OR_AND.name
    n, b = engine.n, batch

    def run(engine: GraphEngine, sources: Array) -> BFSBatchResult:
        step = engine.batch_step_fn(policy)
        rows = jnp.arange(b)
        frontier = jnp.zeros((b, n), sr.dtype).at[rows, sources].set(1)
        visited = jnp.zeros((b, n), jnp.int32).at[rows, sources].set(1)
        levels = jnp.full((b, n), -1, jnp.int32).at[rows, sources].set(0)
        frontier = _constrain_block(frontier, mesh, axis_name)
        visited = _constrain_block(visited, mesh, axis_name)
        levels = _constrain_block(levels, mesh, axis_name)

        def cond(state):
            _f, _v, _l, it, done, _its, _d, _k = state
            return (~jnp.all(done)) & (it < max_iters)

        @jax.named_scope("bfs_step")
        def body(state):
            frontier, visited, levels, it, done, iters, dens, kern = state
            active = ~done
            density = density_of_batch(frontier, sr, engine.n_true)
            used = _kernel_codes(policy, density, engine.threshold)
            y = step(frontier, density)
            nf = jnp.where((y != sr.zero) & (visited == 0),
                           jnp.asarray(1, sr.dtype), jnp.asarray(0, sr.dtype))
            nf = jnp.where(active[:, None], nf, jnp.zeros_like(nf))
            levels = jnp.where((nf != 0) & (levels < 0), it + 1, levels)
            visited = jnp.where(nf != 0, 1, visited)
            newly_done = jnp.sum(nf, axis=1) == 0
            iters = jnp.where(active, it + 1, iters)
            dens = _masked_trace_update(dens, it, active, density)
            kern = _masked_trace_update(kern, it, active, used)
            return (nf, visited, levels, it + 1, done | newly_done,
                    iters, dens, kern)

        state0 = (frontier, visited, levels, jnp.asarray(0, jnp.int32),
                  jnp.zeros((b,), bool), jnp.zeros((b,), jnp.int32),
                  jnp.full((b, max_iters), -1.0, jnp.float32),
                  jnp.full((b, max_iters), -1, jnp.int32))
        _f, _v, levels, _it, _done, iters, dens, kern = jax.lax.while_loop(
            cond, body, state0)
        return BFSBatchResult(levels[:, : engine.n_true], iters, dens, kern)

    return BatchRunner(engine, run, mesh)


def _relax_block(engine: GraphEngine, policy: str, max_iters: int,
                 dist: Array, changed: Array) -> SSSPBatchResult:
    """The ⟨min,+⟩ re-relaxation loop over a [B, n] state block, shared by
    the cold-start SSSP runner and the warm-start resume runner: relax
    only from rows' ``changed`` frontiers until no distance improves.
    Any (dist, changed) with dist ≥ the true fixpoint pointwise and every
    possible improvement reachable from a changed vertex converges to the
    exact fixpoint — the property graphs/dynamic.py's incremental
    recompute is built on."""
    sr = engine.sr
    b = dist.shape[0]
    step = engine.batch_step_fn(policy)

    def cond(state):
        _di, _ch, it, done, _its, _d, _k = state
        return (~jnp.all(done)) & (it < max_iters)

    @jax.named_scope("sssp_step")
    def body(state):
        dist, changed, it, done, iters, dens, kern = state
        active = ~done
        density = density_of_batch(changed, sr, engine.n_true)
        used = _kernel_codes(policy, density, engine.threshold)
        cand = step(changed, density)
        new_dist = jnp.minimum(dist, cand)
        new_changed = jnp.where(new_dist < dist, new_dist, jnp.inf)
        new_dist = jnp.where(active[:, None], new_dist, dist)
        new_changed = jnp.where(active[:, None], new_changed,
                                jnp.full_like(new_changed, jnp.inf))
        newly_done = jnp.sum((new_changed != jnp.inf).astype(jnp.int32),
                             axis=1) == 0
        iters = jnp.where(active, it + 1, iters)
        dens = _masked_trace_update(dens, it, active, density)
        kern = _masked_trace_update(kern, it, active, used)
        return (new_dist, new_changed, it + 1, done | newly_done,
                iters, dens, kern)

    state0 = (dist, changed, jnp.asarray(0, jnp.int32),
              jnp.zeros((b,), bool), jnp.zeros((b,), jnp.int32),
              jnp.full((b, max_iters), -1.0, jnp.float32),
              jnp.full((b, max_iters), -1, jnp.int32))
    dist, _ch, _it, _done, iters, dens, kern = jax.lax.while_loop(
        cond, body, state0)
    return SSSPBatchResult(dist[:, : engine.n_true], iters, dens, kern)


def make_sssp_multi(engine: GraphEngine, batch: int, max_iters: int = 64,
                    policy: str = "adaptive", mesh: Mesh | None = None,
                    axis_name: str = "batch"
                    ) -> Callable[[Array], SSSPBatchResult]:
    """Build a jitted runner: sources [B] int32 -> SSSPBatchResult."""
    sr = engine.sr
    assert sr.name == MIN_PLUS.name
    n, b = engine.n, batch

    def run(engine: GraphEngine, sources: Array) -> SSSPBatchResult:
        rows = jnp.arange(b)
        dist = jnp.full((b, n), jnp.inf, jnp.float32).at[rows, sources].set(0.0)
        changed = jnp.full((b, n), jnp.inf, jnp.float32
                           ).at[rows, sources].set(0.0)
        dist = _constrain_block(dist, mesh, axis_name)
        changed = _constrain_block(changed, mesh, axis_name)
        return _relax_block(engine, policy, max_iters, dist, changed)

    return BatchRunner(engine, run, mesh)


def make_relax_multi(engine: GraphEngine, batch: int, max_iters: int = 64,
                     policy: str = "adaptive", mesh: Mesh | None = None,
                     axis_name: str = "batch"
                     ) -> Callable[[Array, Array], SSSPBatchResult]:
    """Build a jitted warm-start runner: (dist0, changed0) [B, n_true]
    f32 blocks -> SSSPBatchResult. Seeding ``dist0`` = previous distances
    with stale entries reset to +inf and ``changed0`` = the delta frontier
    (finite only where re-relaxation must start) is the incremental
    BFS/SSSP path of graphs/dynamic.py; seeding the cold start
    (source rows 0, rest +inf) reproduces :func:`make_sssp_multi`
    bit-for-bit — same loop, same ops (tests/test_multi_query.py)."""
    sr = engine.sr
    assert sr.name == MIN_PLUS.name
    n = engine.n

    def run(engine: GraphEngine, dist0: Array,
            changed0: Array) -> SSSPBatchResult:
        pad = ((0, 0), (0, n - dist0.shape[1]))
        dist = jnp.pad(dist0, pad, constant_values=jnp.inf)
        changed = jnp.pad(changed0, pad, constant_values=jnp.inf)
        dist = _constrain_block(dist, mesh, axis_name)
        changed = _constrain_block(changed, mesh, axis_name)
        return _relax_block(engine, policy, max_iters, dist, changed)

    return BatchRunner(engine, run, mesh)


def make_ppr_multi(engine: GraphEngine, batch: int, alpha: float = 0.85,
                   max_iters: int = 50, tol: float = 1e-6,
                   policy: str = "adaptive", mesh: Mesh | None = None,
                   axis_name: str = "batch"
                   ) -> Callable[[Array], PPRBatchResult]:
    """Build a jitted runner: sources [B] int32 -> PPRBatchResult."""
    sr = engine.sr
    assert sr.name == PLUS_TIMES.name
    n, b = engine.n, batch

    def run(engine: GraphEngine, sources: Array) -> PPRBatchResult:
        step = engine.batch_step_fn(policy)
        rows = jnp.arange(b)
        e_s = jnp.zeros((b, n), jnp.float32).at[rows, sources].set(1.0)
        e_s = _constrain_block(e_s, mesh, axis_name)

        def cond(state):
            _r, it, res, _its, _d, _k = state
            return jnp.any(res > tol) & (it < max_iters)

        @jax.named_scope("ppr_step")
        def body(state):
            r, it, res, iters, dens, kern = state
            active = res > tol
            density = density_of_batch(r, sr, engine.n_true)
            used = _kernel_codes(policy, density, engine.threshold)
            pr = step(r, density)
            r_new = (1.0 - alpha) * e_s + alpha * pr
            res_new = jnp.sum(jnp.abs(r_new - r), axis=1)
            r = jnp.where(active[:, None], r_new, r)
            res = jnp.where(active, res_new, res)
            iters = jnp.where(active, it + 1, iters)
            dens = _masked_trace_update(dens, it, active, density)
            kern = _masked_trace_update(kern, it, active, used)
            return (r, it + 1, res, iters, dens, kern)

        state0 = (e_s, jnp.asarray(0, jnp.int32),
                  jnp.full((b,), jnp.inf, jnp.float32),
                  jnp.zeros((b,), jnp.int32),
                  jnp.full((b, max_iters), -1.0, jnp.float32),
                  jnp.full((b, max_iters), -1, jnp.int32))
        r, _it, res, iters, dens, kern = jax.lax.while_loop(cond, body, state0)
        return PPRBatchResult(r[:, : engine.n_true], iters, dens, kern, res)

    return BatchRunner(engine, run, mesh)


_MAKERS = {"bfs": make_bfs_multi, "sssp": make_sssp_multi,
           "ppr": make_ppr_multi, "relax": make_relax_multi}

# Builds are serialized under one module lock: the async serving layer
# may drain two servers sharing an engine from different threads, and a
# racing double-build would waste a compile (results would still agree).
_runner_lock = threading.Lock()


def _cached_runner(engine: GraphEngine, alg: str, batch: int, mesh,
                   axis_name: str, **kwargs):
    """One jitted runner per (engine, alg, batch, options) — GraphEngine is
    an unhashable dataclass, so runners live in its instance __dict__."""
    key = (alg, batch, id(mesh), axis_name, tuple(sorted(kwargs.items())))
    cache = engine.__dict__.setdefault("_multi_runners", {})
    if key not in cache:
        with _runner_lock:
            if key not in cache:      # double-checked: lost races reuse
                cache[key] = _MAKERS[alg](engine, batch, mesh=mesh,
                                          axis_name=axis_name, **kwargs)
    return cache[key]


def _as_sources(sources) -> Array:
    src = jnp.asarray(np.asarray(sources), jnp.int32)
    assert src.ndim == 1, "sources must be a flat [B] list/array"
    return src


def bfs_multi(engine: GraphEngine, sources, max_iters: int = 64,
              policy: str = "adaptive", mesh: Mesh | None = None,
              axis_name: str = "batch") -> BFSBatchResult:
    """Multi-source BFS; row b equals bfs(engine, sources[b])."""
    src = _as_sources(sources)
    run = _cached_runner(engine, "bfs", int(src.shape[0]), mesh, axis_name,
                         max_iters=max_iters, policy=policy)
    return run(src)


def sssp_multi(engine: GraphEngine, sources, max_iters: int = 64,
               policy: str = "adaptive", mesh: Mesh | None = None,
               axis_name: str = "batch") -> SSSPBatchResult:
    """Multi-source SSSP; row b equals sssp(engine, sources[b])."""
    src = _as_sources(sources)
    run = _cached_runner(engine, "sssp", int(src.shape[0]), mesh, axis_name,
                         max_iters=max_iters, policy=policy)
    return run(src)


def relax_multi(engine: GraphEngine, dist0, changed0, max_iters: int = 64,
                policy: str = "adaptive", mesh: Mesh | None = None,
                axis_name: str = "batch") -> SSSPBatchResult:
    """Warm-start ⟨min,+⟩ re-relaxation from explicit [B, n_true] state
    blocks (the delta-frontier path of graphs/dynamic.py): ``dist0`` holds
    the surviving distances (+inf where stale or unknown), ``changed0``
    the seed frontier (+inf everywhere relaxation need not start). Runs
    the exact loop of :func:`sssp_multi` on the cached per-batch runner."""
    d0 = jnp.asarray(np.asarray(dist0, np.float32))
    c0 = jnp.asarray(np.asarray(changed0, np.float32))
    assert d0.ndim == 2 and d0.shape == c0.shape, (d0.shape, c0.shape)
    run = _cached_runner(engine, "relax", int(d0.shape[0]), mesh, axis_name,
                         max_iters=max_iters, policy=policy)
    return run(d0, c0)


def traverse_multi_buckets(engine: GraphEngine, alg: str, buckets,
                           pipeline_depth: int = 2, mesh: Mesh | None = None,
                           axis_name: str = "batch", materialize=None,
                           pad_to: int | None = None, **kwargs) -> list:
    """Pipelined bucket mode: run several source buckets through the cached
    batched runners, keeping up to ``pipeline_depth`` buckets in flight so
    bucket *t+1*'s dispatch (and device compute) overlaps the host-side
    await + conversion of bucket *t* (core.pipeline.pipeline_buckets).

    ``materialize(bucket, result) -> value`` runs inside the overlap
    window, in submission order, and receives the bucket *as submitted* —
    put the host-side payload conversion there (the server does); the
    default just blocks and returns the *BatchResult. ``pad_to`` pads
    every issued bucket to that batch size by repeating its last source
    (one compiled runner for all buckets; result rows past the submitted
    bucket's length are padding). Without it, mixed-size buckets compile
    one runner per distinct size. ``pipeline_depth=0`` is the strictly
    sequential drain; results are identical at any depth — the same
    jitted runner consumes the same buckets, only host sync order changes
    (asserted in tests/test_multi_query.py). ``kwargs`` are the
    per-algorithm maker options (max_iters / policy / alpha / tol).
    Returns one materialised value per bucket, in submission order.
    """
    def issue(bucket):
        sources = list(bucket)
        if pad_to is not None and len(sources) < pad_to:
            sources = sources + [sources[-1]] * (pad_to - len(sources))
        src = _as_sources(sources)
        run = _cached_runner(engine, alg, int(src.shape[0]), mesh,
                             axis_name, **kwargs)
        return run(src)

    if materialize is None:
        materialize = lambda _b, res: jax.block_until_ready(res)  # noqa: E731
    return pipeline_buckets(issue, materialize, buckets,
                            depth=pipeline_depth)


def partitioned_matvec(graph, sr, mesh, strategy: str = "auto",
                       balance: str | None = None, kernel: str = "spmv",
                       fmt: str | None = None, frontier_density: float = 1.0,
                       weighted: bool = False, normalize: bool = False,
                       seed: int = 0, batched: bool = False,
                       topology: str = "auto", merge_order: str | None = None):
    """Partition ``graph``'s transposed adjacency over ``mesh`` (axes
    ``dr``/``dc``) and build its distributed matvec — the Fig.-3 execution
    path of the many-query layer, with the partition decided by the
    cost-model planner.

    ``strategy="auto"`` lets :func:`repro.graphs.cost_model
    .choose_partition` pick strategy+balance from the graph's degree
    histogram and ``frontier_density``; a fixed ``"row"``/``"col"``/
    ``"2d"`` (optionally suffixed ``:rows``/``:nnz``, or with an explicit
    ``balance``) pins it while still producing the planner's cost table.

    ``topology="auto"`` likewise takes the Merge collective the planner
    priced cheapest (``choice.merge``/``choice.merge_order`` — see
    :func:`repro.graphs.cost_model.choose_merge`); a fixed ``"flat"``/
    ``"ring"``/``"tree"``/``"staged2d"`` pins it (``merge_order``
    selects the staged-2D exchange order, default ``"rc"``).

    Returns ``(pm, fn, choice)``: the PartitionedMatrix (its ``plan``
    carries the shard/unshard layout helpers), the jit-ready matvec
    (``batched=True`` builds the [B, n]-block variant), and the
    :class:`~repro.graphs.cost_model.PlannerChoice`.
    """
    from repro.core.distributed import (
        make_distributed_batched_matvec, make_distributed_matvec,
    )
    from repro.core.partition import partition
    from repro.graphs.cost_model import (
        candidate_space, parse_strategy, plan_for_graph,
    )
    from repro.graphs.engine import edge_values

    strategy, balance = parse_strategy(strategy, balance)
    strategies, balances = candidate_space(strategy, balance)
    n_dev = mesh.shape["dr"] * mesh.shape["dc"]
    grid2d = (mesh.shape["dr"], mesh.shape["dc"])
    choice = plan_for_graph(graph, n_devices=n_dev, grid2d=grid2d,
                            kernel=kernel, frontier_density=frontier_density,
                            strategies=strategies, balances=balances)
    vals = edge_values(graph, sr, weighted, seed, normalize)
    fmt = fmt or ("csc" if kernel == "spmspv" else "csr")
    rows = graph.cols.astype(np.int64)   # transposed: pull from in-neighbours
    cols = graph.rows.astype(np.int64)
    pm = partition(rows, cols, vals, choice.plan.shape, choice.grid, fmt, sr,
                   plan=choice.plan)
    if topology == "auto":
        topology, merge_order = choice.merge, choice.merge_order
    maker = (make_distributed_batched_matvec if batched
             else make_distributed_matvec)
    fn = maker(mesh, pm, sr, choice.strategy, kernel=kernel,
               topology=topology, merge_order=merge_order or "rc")
    return pm, fn, choice


def ppr_multi(engine: GraphEngine, sources, alpha: float = 0.85,
              max_iters: int = 50, tol: float = 1e-6,
              policy: str = "adaptive", mesh: Mesh | None = None,
              axis_name: str = "batch") -> PPRBatchResult:
    """Multi-source PPR; row b equals ppr(engine, sources[b])."""
    src = _as_sources(sources)
    run = _cached_runner(engine, "ppr", int(src.shape[0]), mesh, axis_name,
                         alpha=alpha, max_iters=max_iters, tol=tol,
                         policy=policy)
    return run(src)

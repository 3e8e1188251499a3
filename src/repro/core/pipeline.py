"""Pipelined phase-overlap execution engine — the paper's non-blocking-DMA
recommendation, modelled in software.

ALPHA-PIM measures that *blocking* host-mediated transfers dominate graph
runtime on UPMEM and explicitly calls for "improved DMA engines with
non-blocking capabilities" and direct inter-core networks. On a JAX mesh
the equivalent capability already exists — dispatch is asynchronous — but
the sequential engine never exploits it: the per-phase accounting schedule
(benchmarks/phases.py) synchronises the host after every phase. The
four-phase vocabulary (Load / Kernel / Retrieve / Merge) is defined once
in :mod:`repro.core.distributed`; this module only adds *when* those
phases run relative to each other.

Two pipelines model the fix at the two granularities the repo executes:

* :func:`iterate_phases` — the iteration-level software pipeline over the
  per-phase closures of :func:`repro.core.distributed.build_phase_fns`.
  Phases are dispatched without host synchronisation, so iteration *t*'s
  Retrieve+Merge (and the inter-iteration feedback reshard) overlap the
  dispatch and Load of iteration *t+1*; at most ``depth`` iterations run
  ahead of the last materialised one (``depth=2`` is classic double
  buffering). ``depth=0`` is the **blocking fallback** — one
  ``block_until_ready`` per phase, the schedule the paper measures on
  UPMEM — and is bit-identical to every other depth by construction: the
  same compiled executables consume the same inputs in the same order;
  only the host sync points move (asserted in tests/test_distributed.py).

* :func:`pipeline_buckets` — the bucket-level pipeline behind the
  multi-query server: dispatching query bucket *t+1*'s jitted traversal
  overlaps the host-side materialisation of bucket *t*'s results. It is
  generic over an ``issue``/``materialize`` pair so
  :func:`repro.graphs.multi.traverse_multi_buckets` and
  :class:`repro.serve.graph_engine.GraphQueryServer` share one
  implementation.

Both pipelines are agnostic to *how* the Merge phase moves bytes: the
closures build_phase_fns hands over may run any
:mod:`repro.core.collectives` topology (flat host-bounce, ring, tree,
staged-2D) — the collective executes inside the Merge closure's
shard_map, so phase overlap and the ``depth=0`` bit-equality guarantee
are preserved unchanged under every topology.

Overlap is quantified by ``benchmarks/pipeline_overlap.py``: pipelined
wall time vs the sequential per-phase sum, per Fig.-3 strategy and
Table-2 family.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Callable, Iterable, Mapping, Optional, Sequence

import jax

from repro.obs import trace

Array = jax.Array
#: A build_phase_fns product: phase name -> closure (or None when the
#: strategy folds that phase away). See repro.core.distributed.
PhaseFns = Mapping[str, Optional[Callable]]


def _no_sync(a):
    return a


def run_phases_once(fns: PhaseFns, parts, x: Array,
                    sync: Callable[[Any], Any] = _no_sync) -> Array:
    """One Load → Kernel → Retrieve+Merge → feedback step through a
    :func:`~repro.core.distributed.build_phase_fns` dict.

    ``sync`` is applied to every phase's output: the default leaves the
    dispatch asynchronous (non-blocking DMA); passing
    ``jax.block_until_ready`` reproduces the paper's blocking schedule.
    Strategies with a folded phase (``None`` entry) skip it; a strategy
    whose Kernel is only available fused (compressed-Load rows) falls back
    to the ``e2e`` closure for the compute step.

    ``build_phase_fns(fused=True)`` dicts run here unchanged: their
    ``kernel`` closure already contains the Retrieve+Merge (the streaming
    kernel scatters chunk-major partials straight into
    collectives.merge_chunks), so ``retrieve_merge`` is None and the
    pipeline simply has one less phase boundary to overlap — the overlap
    moved *inside* the kernel program.
    """
    load = fns.get("load")
    kern = fns.get("kernel")
    rm = fns.get("retrieve_merge")
    feedback = fns.get("feedback")

    if kern is None:
        # Kernel only available fused (compressed-Load rows): the e2e
        # closure runs Load/Kernel/Retrieve/Merge in one program and
        # already lands in the canonical input layout.
        return sync(fns["e2e"](parts, x))
    xf = sync(load(parts, x)) if load is not None else x
    y = sync(kern(parts, x, xf))
    if rm is not None:
        y = sync(rm(parts, y))
    if feedback is not None:
        y = sync(feedback(y))
    return y


def iterate_phases(fns: PhaseFns, parts, x0: Array, n_iters: int,
                   depth: int = 2) -> Array:
    """Iterate ``x ← A ⊕.⊗ x`` for ``n_iters`` steps through per-phase
    closures, keeping at most ``depth`` iterations in flight.

    ``depth >= 1`` (pipelined): every phase of every iteration is
    dispatched without host synchronisation; the host only blocks when
    more than ``depth`` iteration outputs are pending (backpressure), so
    the runtime is free to overlap iteration *t*'s Retrieve+Merge with the
    Load of *t+1* — the paper's proposed non-blocking schedule.

    ``depth <= 0`` (blocking fallback): ``block_until_ready`` after every
    phase — the sequential schedule benchmarks/phases.py times. Both modes
    run the identical executables on identical inputs, so results are
    bit-identical at any depth.

    Returns the final vector, materialised (blocked) on the caller's side.
    """
    if n_iters < 0:
        raise ValueError(f"n_iters must be >= 0, got {n_iters}")
    # Observability: one None check when tracing is disabled. With a
    # tracer installed the individual phases already trace themselves
    # (build_phase_fns wraps each closure in a blocking span — the
    # pipeline degenerates to the blocking schedule while observed, by
    # design: that is the schedule whose per-phase sums mean anything);
    # here we only add the backpressure-drain windows, the part of the
    # overlap no phase span can see.
    t = trace.active()
    x = x0
    if depth <= 0:
        for _ in range(n_iters):
            x = run_phases_once(fns, parts, x, sync=jax.block_until_ready)
        return jax.block_until_ready(x)

    in_flight: deque[Array] = deque()
    for _ in range(n_iters):
        x = run_phases_once(fns, parts, x)
        in_flight.append(x)
        while len(in_flight) > depth:
            head = in_flight.popleft()
            if t is None:
                jax.block_until_ready(head)
            else:
                with t.span("pipeline/drain", depth=depth):
                    jax.block_until_ready(head)
    if t is None:
        return jax.block_until_ready(x)
    with t.span("pipeline/drain", depth=depth, final=True):
        return jax.block_until_ready(x)


def pipeline_buckets(issue: Callable[[Any], Any],
                     materialize: Callable[[Any, Any], Any],
                     items: Sequence[Any] | Iterable[Any],
                     depth: int = 2) -> list:
    """Bounded-depth software pipeline over independent work buckets.

    ``issue(item)`` dispatches device work and returns a handle without
    blocking (JAX async dispatch makes any jitted call qualify);
    ``materialize(item, handle)`` blocks on the handle and converts it to
    the caller's result type. At most ``depth`` issued-but-unmaterialised
    handles are kept in flight, so bucket *t+1*'s dispatch (and device
    compute) overlaps bucket *t*'s host-side materialisation.

    ``depth <= 0`` degenerates to the strictly sequential
    issue-then-materialize loop. Results are returned in item order and
    are identical at any depth — the pipeline only reorders host syncs,
    never device work.
    """
    results: list = []
    pending: deque[tuple[Any, Any]] = deque()
    limit = max(0, depth)
    # the issue window (dispatch) and the materialize window (the host
    # sync the pipeline hides) are spans, indexed by bucket; both are
    # the shared no-op while nothing observes them
    n_issued = 0
    for item in items:
        with trace.span("pipeline/issue", bucket=n_issued, depth=limit):
            pending.append((item, issue(item)))
        n_issued += 1
        while len(pending) > limit:
            it, handle = pending.popleft()
            with trace.span("pipeline/materialize",
                            bucket=n_issued - len(pending) - 1, depth=limit):
                results.append(materialize(it, handle))
    while pending:
        it, handle = pending.popleft()
        with trace.span("pipeline/materialize",
                        bucket=n_issued - len(pending) - 1, depth=limit):
            results.append(materialize(it, handle))
    return results

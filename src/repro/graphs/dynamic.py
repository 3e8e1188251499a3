"""Streaming graph updates: versioned snapshots + incremental recompute.

The paper's data-movement accounting (§5: Load/Retrieve dominate) makes
*recompute-from-scratch on every edge change* the worst possible serving
policy — the whole graph re-crosses the fabric for a delta that touched a
handful of vertices. This module is the repo's answer:

* :class:`DynamicGraph` — a mutable store over **immutable** canonical
  :class:`~repro.graphs.datasets.Graph` snapshots. Each applied
  :class:`~repro.core.delta.EdgeDelta` batch produces a new snapshot
  whose edge list is bit-for-bit what a from-scratch datasets-style
  construction over the updated edge set would build, under a
  monotonically-versioned fingerprint (``v<k>:<content-hash>``).

* **Incremental recompute** — given the previous answers and the delta,
  re-derive the new-snapshot answers from the *delta frontier* instead of
  from cold start, element-equal to cold recompute:

  - BFS / SSSP: delta-frontier re-relaxation. Retained distances stay;
    vertices whose values a deletion may have invalidated (everything in
    the new-graph components of deleted-edge endpoints — a sound
    superset) reset to +inf; re-relaxation seeds only from the touched
    vertices and the stale region (graphs/multi.py:relax_multi, the same
    jitted ⟨min,+⟩ loop as cold SSSP). BFS rides the identical machinery
    over a unit-weight ⟨min,+⟩ engine — levels are unit distances, small
    integers, exact in f32.
  - Connected components: label repair — old components containing any
    touched vertex reset to own-id labels, everything else keeps its
    label, then the ordinary min-label flood converges in rounds
    proportional to the *repaired region's* diameter.
  - PageRank: warm restart from the previous rank vector
    (graphs/ppr.py:pagerank(r0=...)) — same fixpoint, fewer iterations.

Exactness requires engines whose edge values are functions of graph
*content*, not edge-list position: SSSP engines over delta snapshots must
be built with ``content_keyed=True``
(graphs/engine.py:content_keyed_weights); unit/normalized weights already
are. Element-traffic accounting (``traffic_of``) counts the frontier
elements each kernel invocation consumes — the Load-phase currency the
paper budgets — so benchmarks/dynamic_updates.py can show incremental
< cold in the metric that matters, not just wall time.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import numpy as np

from repro.core.delta import (
    EdgeDelta, apply_edge_delta, canonicalize, touched_vertices,
)
from repro.core.semiring import MIN_PLUS, MIN_TIMES
from repro.graphs.analytics import CCResult, connected_components
from repro.graphs.datasets import Graph
from repro.graphs.engine import GraphEngine
from repro.graphs.multi import SSSPBatchResult, relax_multi
from repro.graphs.ppr import PPRResult, pagerank


class DynamicGraph:
    """Versioned store over immutable Graph snapshots.

    ``apply(delta)`` advances to a new snapshot (set semantics, canonical
    edge order — see core/delta.py) and bumps the version; every snapshot
    handed out stays valid forever, so in-flight queries keep draining
    against the graph they were submitted under while new queries see the
    new version (the consistency model serve/graph_engine.py:mutate
    builds on)."""

    def __init__(self, graph: Graph, version: int = 0):
        self._graph = graph
        self.version = version

    @property
    def snapshot(self) -> Graph:
        return self._graph

    @property
    def fingerprint(self) -> str:
        """Monotonically-versioned content fingerprint: the version makes
        successive fingerprints ordered even across an apply/undo cycle
        that returns to an earlier edge set."""
        return f"v{self.version}:{self._graph.fingerprint()}"

    def apply(self, delta: EdgeDelta) -> Graph:
        """Apply one delta batch; returns (and switches to) the new
        immutable snapshot. A no-op delta still bumps the version — the
        caller asked for a new epoch and gets one."""
        rows, cols = apply_edge_delta(
            self._graph.rows, self._graph.cols, self._graph.n, delta)
        self._graph = dataclasses.replace(self._graph, rows=rows, cols=cols)
        self.version += 1
        return self._graph


def traffic_of(result) -> float:
    """Element traffic of one batched traversal: frontier nonzeros the
    kernel consumed, summed over queries and iterations (the runner's
    per-row ``consumed`` count — the Load-phase element accounting of
    core/distributed.py, applied to the single-device path)."""
    return float(np.sum(np.asarray(result.consumed, np.float64)))


class DeltaRepair(NamedTuple):
    """The delta's blast radius, computed once per (snapshot, delta) and
    shared across every incremental traversal that follows."""

    touched: np.ndarray        # sorted unique endpoints of the delta
    stale: np.ndarray | None   # bool [n_true] possibly-invalidated set
    traffic: float             # reachability-pass element traffic


def plan_repair(engine: GraphEngine, delta: EdgeDelta) -> DeltaRepair:
    """Compute the delta's repair plan against the **new** snapshot's
    ⟨min,+⟩ engine (unit or weighted — only finiteness is read).

    Insert-only deltas invalidate nothing: old distances are still valid
    lower bounds... exactly valid values, only *improvable* via the new
    edges. Deletions may invalidate any vertex whose old shortest path
    crossed a deleted edge; every such vertex lies in the new-graph
    component of some deleted-edge endpoint (any old path from the edge
    onward either survives — staying inside that component — or dies at
    another deleted edge, inductively). One multi-seed reachability relax
    from all deleted endpoints marks that superset."""
    assert engine.sr.name == MIN_PLUS.name, engine.sr.name
    n_true = engine.n_true
    delta = canonicalize(delta, n_true)
    touched = touched_vertices(delta)
    if delta.n_deletes == 0:
        return DeltaRepair(touched, None, 0.0)
    seeds = np.unique(np.concatenate([delta.delete_rows, delta.delete_cols]))
    d0 = np.full((1, n_true), np.inf, np.float32)
    d0[0, seeds] = 0.0
    # the reach pass runs to its fixpoint (a truncated stale set would
    # leave invalid distances in place), as every relax does
    res = relax_multi(engine, d0, d0.copy())
    stale = np.isfinite(np.asarray(res.dist[0]))
    return DeltaRepair(touched, stale, traffic_of(res))


class IncrementalTraversal(NamedTuple):
    values: np.ndarray         # levels int32 / dist f32, [B, n_true]
    result: SSSPBatchResult    # the relax result (per-row counters)
    traffic: float             # relax traffic (excl. the shared repair pass)
    repair: DeltaRepair


def _incremental_relax(engine: GraphEngine, sources, old_dist: np.ndarray,
                       delta: EdgeDelta, repair: DeltaRepair | None,
                       policy: str) -> IncrementalTraversal:
    """Shared BFS/SSSP delta-frontier re-relaxation: reset the stale
    region, restore the sources' zeros, seed ``changed`` from the touched
    vertices plus the stale region, relax to fixpoint."""
    n_true = engine.n_true
    delta = canonicalize(delta, n_true)
    if repair is None:
        repair = plan_repair(engine, delta)
    d0 = np.array(old_dist, np.float32, copy=True)
    assert d0.ndim == 2 and d0.shape[1] == n_true, d0.shape
    rows = np.arange(d0.shape[0])
    src = np.asarray(sources, np.int64).reshape(-1)
    assert src.shape[0] == d0.shape[0], (src.shape, d0.shape)
    seed = np.zeros(n_true, bool)
    seed[repair.touched] = True
    if repair.stale is not None:
        d0[:, repair.stale] = np.inf
        seed |= repair.stale
    d0[rows, src] = 0.0          # the source is correct in every epoch
    changed0 = np.where(seed[None, :] & np.isfinite(d0), d0,
                        np.float32(np.inf)).astype(np.float32)
    res = relax_multi(engine, d0, changed0, policy=policy)
    dist = np.asarray(res.dist)
    return IncrementalTraversal(dist, res, traffic_of(res), repair)


def sssp_incremental(engine: GraphEngine, sources, old_dist,
                     delta: EdgeDelta, repair: DeltaRepair | None = None,
                     policy: str = "adaptive") -> IncrementalTraversal:
    """Incremental SSSP: ``old_dist`` [B, n_true] from the previous
    snapshot (+inf = unreachable), ``engine`` a **content-keyed** weighted
    ⟨min,+⟩ engine over the new snapshot. Element-equal to a cold
    sssp_multi on the new snapshot: the warm state is pointwise ≥ the
    fixpoint with every improvement reachable from a seeded vertex, and
    the ⟨min,+⟩ fixpoint over integer-valued weights is unique and exact
    in f32 (tests/test_dynamic.py, benchmarks/dynamic_updates.py)."""
    return _incremental_relax(engine, sources, old_dist, delta, repair,
                              policy)


def bfs_incremental(engine: GraphEngine, sources, old_levels,
                    delta: EdgeDelta, repair: DeltaRepair | None = None,
                    policy: str = "adaptive") -> IncrementalTraversal:
    """Incremental BFS as unit-weight incremental SSSP: ``old_levels``
    [B, n_true] int (-1 = unreached) from the previous snapshot,
    ``engine`` a unit-weight ⟨min,+⟩ engine (build_engine(g, MIN_PLUS,
    weighted=False)) over the new snapshot. ``values`` converts back to
    BFS levels (int32, -1 unreached) — element-equal to a cold bfs_multi
    on the new snapshot since levels are unit distances."""
    lev = np.asarray(old_levels)
    old_dist = np.where(lev < 0, np.float32(np.inf),
                        lev.astype(np.float32))
    out = _incremental_relax(engine, sources, old_dist, delta, repair,
                             policy)
    levels = np.where(np.isfinite(out.values),
                      out.values, -1.0).astype(np.int32)
    return IncrementalTraversal(levels, out.result, out.traffic, out.repair)


def cc_incremental(engine: GraphEngine, old_labels, delta: EdgeDelta,
                   max_iters: int | None = None) -> CCResult:
    """Incremental connected-components label repair. Inserts only ever
    *merge* components, and min-flooding the old labels over the new
    graph already resolves a merge exactly (the smaller old minimum wins
    across the new edge) — so old labels flow through untouched. Deletes
    can *split*, which makes a component's old minimum unreachable for
    part of it: every old component containing a deleted-edge endpoint
    resets to own-id labels and recomputes from scratch. Untouched
    components are unchanged whole components (any edge change incident
    to one would touch it), so the flood (graphs/analytics.py) converges
    in rounds ~ the repaired/merged region's radius — element-equal to
    the cold run, integer labels, exact in f32."""
    assert engine.sr.name == MIN_TIMES.name, engine.sr.name
    n_true = engine.n_true
    delta = canonicalize(delta, n_true)
    labels = np.asarray(old_labels)
    assert labels.shape == (n_true,), labels.shape
    if delta.n_deletes:
        cut = np.unique(np.concatenate([delta.delete_rows,
                                        delta.delete_cols]))
        stale = np.isin(labels, labels[cut])
        seed = np.where(stale, np.arange(n_true, dtype=labels.dtype), labels)
    else:
        seed = labels
    return connected_components(engine, max_iters=max_iters, labels0=seed)


def pagerank_warm(engine: GraphEngine, old_rank, alpha: float = 0.85,
                  max_iters: int = 50, tol: float = 1e-6,
                  policy: str = "spmv") -> PPRResult:
    """Warm-restart PageRank on the new snapshot from the previous rank
    vector: the power iteration's fixpoint is a property of the graph, so
    starting near it (small deltas move it little) pays fewer iterations
    for the same ε — the iteration-count win
    benchmarks/dynamic_updates.py reports per family."""
    return pagerank(engine, alpha=alpha, max_iters=max_iters, tol=tol,
                    policy=policy, r0=old_rank)

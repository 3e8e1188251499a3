"""Faults planted under the served path, for the tests that check that the
comparison turns ``correct`` false. Each takes a pytest ``MonkeyPatch``.
"""
from __future__ import annotations

import numpy as np

UNREACHED = {"levels": -1, "dist": np.inf, "rank": 0.0}


def state_unchanged(mp):
    """Every traversal step returns its input state."""
    from repro.graphs.engine import GraphEngine

    mp.setattr(GraphEngine, "batch_step_fn",
               lambda self, policy: (lambda xs, _d: xs))


def half_batch_left_out(mp):
    """The second half of every bucket's queries is never computed: their
    rows reach the payloads as if no iteration had run."""
    from repro.serve.graph_engine import GraphQueryServer

    make = GraphQueryServer._payloads

    def payloads(rows, iters, sources):
        rows = {key: np.array(v) for key, v in rows.items()}
        for key, v in rows.items():
            if key in UNREACHED:
                v[len(sources) // 2:len(sources)] = UNREACHED[key]
        return make(rows, iters, sources)

    mp.setattr(GraphQueryServer, "_payloads", staticmethod(payloads))


def answer_altered(mp):
    """Every answer is altered where it is produced: the root's own entry."""
    from repro.serve.graph_engine import GraphQueryServer

    make = GraphQueryServer._payloads

    def payloads(rows, iters, sources):
        out = make(rows, iters, sources)
        for src, p in out.items():
            for key in UNREACHED:
                if key in p:
                    v = np.array(p[key])
                    v[src] = 1
                    p[key] = v
        return out

    mp.setattr(GraphQueryServer, "_payloads", staticmethod(payloads))


FAULTS = {"state_unchanged": state_unchanged,
            "half_batch_left_out": half_batch_left_out,
            "answer_altered": answer_altered}


def state_in_bf16(mp):
    """Every float state a traversal step reads is rounded to bfloat16, as
    a state kept in that type would be (integer states are left alone)."""
    import jax.numpy as jnp

    from repro.graphs.engine import GraphEngine

    make = GraphEngine.batch_step_fn

    def batch_step_fn(self, policy):
        step = make(self, policy)

        def rounded(xs, d):
            if jnp.issubdtype(xs.dtype, jnp.floating):
                xs = xs.astype(jnp.bfloat16).astype(xs.dtype)
            return step(xs, d)
        return rounded

    mp.setattr(GraphEngine, "batch_step_fn", batch_step_fn)


# faults that only a float answer compared within a tolerance can show
FLOAT_FAULTS = {"state_in_bf16": state_in_bf16}


def run_tiny(workload: str, fault=None, scale: int = 8,
             seconds: float = 1.0, **kwargs) -> dict:
    """One CPU run of ``workload`` at scale ``scale`` under ``fault``."""
    import time

    import pytest

    from bench import harness, spec

    with pytest.MonkeyPatch.context() as mp:
        # a rate that fills the buckets, so a fault on some rows meets queries
        rate = 50.0 * spec.cell(workload)["traffic"]["batch"]
        if fault is not None:
            fault(mp)
        return harness.run_cell(workload, 20260516, seconds, False,
                                time.perf_counter(), require_tpu=False,
                                config_override={"scale": scale},
                                traffic_override={"rate_per_s": rate},
                                **kwargs)


#!/usr/bin/env python3
"""Smoke run of the served graph traversal on one TPU chip.

Drives the serving path a user calls (``AsyncGraphServer.submit`` ->
``GraphQueryServer.flush`` -> the batched BFS/SSSP/PPR runners) on a
Kronecker graph of scale ``--scale`` (2**S vertices, edge factor 16), checks
the answers against scipy references that share no code with the engine,
runs every Pallas graph kernel once against its jnp oracle, and prints one
JSON line naming the device as the last line of its output.

    python3 chip_smoke.py                  # one chip, S = 21
    python3 chip_smoke.py --four-chips     # the mesh paths, on four chips

``--four-chips`` runs only what exists across chips: the served BFS flush
with its [B, n] query block sharded over a 4-device mesh, and the Fig.-3
partitioned SpMV with its Merge collectives on a 2x2 grid. Both are
compared element-exactly with one-device answers on the same graph.

The script needs a TPU: it exits nonzero, and prints no result, when JAX
finds none. Any failed phase or mismatch exits nonzero too. All work runs
in this one process (a child would find the chip held).

Compile cache: ``JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise the
fixed directory ``.jax_cache`` next to this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
SRC = REPO / "src"

BATCH = 8                 # served bucket size
N_QUERIES = {"bfs": 16, "sssp": 16, "ppr": 8}
N_CHECKED = 4             # roots per algorithm checked against scipy
PPR_ATOL = 1e-6           # max |rank - reference| (ranks sum to <= 1)
FIRST_WINDOW_TIMEOUT_S = 600.0
WARM_WINDOW_TIMEOUT_S = 300.0


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------- set-up
def build_graph(scale: int, seed: int):
    import numpy as np

    from repro.graphs.datasets import rmat_graph

    t0 = time.perf_counter()
    g = rmat_graph(n=2 ** scale, n_edges=16 * 2 ** scale, skew=0.57,
                   seed=seed)
    gen_s = time.perf_counter() - t0
    log(f"set-up: graph S={scale} n={g.n} nnz={g.nnz} "
        f"generate_s={gen_s:.3f}")
    rng = np.random.default_rng(seed)
    live = np.flatnonzero(g.out_degrees() > 0)
    roots = {alg: [int(v) for v in rng.choice(live, k, replace=False)]
             for alg, k in N_QUERIES.items()}
    return g, roots


# ------------------------------------------------------------ references
def _scipy_adjacency(g, data):
    import numpy as np
    import scipy.sparse as sp

    return sp.csr_matrix((np.asarray(data, np.float64), (g.rows, g.cols)),
                         shape=(g.n, g.n))


def bfs_reference(g, roots):
    """Hop counts along edges u -> v; -1 where unreachable."""
    import numpy as np
    from scipy.sparse.csgraph import shortest_path

    adj = _scipy_adjacency(g, np.ones(g.nnz))
    d = shortest_path(adj, method="D", directed=True, unweighted=True,
                      indices=roots)
    return np.where(np.isinf(d), -1, d).astype(np.int64)


def sssp_reference(g, roots, weight_seed: int):
    """Dijkstra over the endpoint-keyed integer weights {1..9}."""
    from scipy.sparse.csgraph import dijkstra

    from repro.graphs.engine import content_keyed_weights

    w = content_keyed_weights(g.rows, g.cols, seed=weight_seed)
    return dijkstra(_scipy_adjacency(g, w), directed=True, indices=roots)


def ppr_reference(g, root: int, alpha: float, iters: int):
    """``iters`` steps of r <- (1 - alpha) e_s + alpha P r, with P the
    column-stochastic transition matrix (u -> v carries 1/outdeg(u))."""
    import numpy as np
    import scipy.sparse as sp

    deg = np.maximum(np.bincount(g.rows, minlength=g.n), 1)
    p = sp.csr_matrix((1.0 / deg[g.rows], (g.cols, g.rows)),
                      shape=(g.n, g.n))
    e = np.zeros(g.n)
    e[root] = 1.0
    r = e.copy()
    for _ in range(iters):
        r = (1.0 - alpha) * e + alpha * (p @ r)
    return r


# ----------------------------------------------------------- served path
def _window(srv, alg: str, roots, timeout: float):
    t0 = time.perf_counter()
    tickets = [srv.submit("kron", alg, r) for r in roots]
    payloads = [tk.wait(timeout=timeout) for tk in tickets]
    return payloads, time.perf_counter() - t0


def served_phase(g, roots):
    """Serve every query through the async server's normal entry points;
    returns ({alg: {root: payload}}, the tenant's GraphQueryServer)."""
    import jax

    from repro.serve.graph_engine import AsyncGraphServer

    answers = {alg: {} for alg in N_QUERIES}
    with AsyncGraphServer(max_wait=0.05) as srv:
        tenant = srv.add_tenant("kron", g, batch_size=BATCH)
        t0 = time.perf_counter()
        for alg in N_QUERIES:
            eng = tenant.engine(alg)
            jax.block_until_ready(eng.mats)
        log(f"set-up: engines (bfs, sssp, ppr) build_s="
            f"{time.perf_counter() - t0:.3f}")
        for alg, alg_roots in roots.items():
            windows = [alg_roots[i:i + BATCH]
                       for i in range(0, len(alg_roots), BATCH)]
            for w, window in enumerate(windows):
                timeout = FIRST_WINDOW_TIMEOUT_S if w == 0 \
                    else WARM_WINDOW_TIMEOUT_S
                payloads, secs = _window(srv, alg, window, timeout)
                label = "compile+first_window_s" if w == 0 \
                    else "warm_window_s"
                iters = [p["iterations"] for p in payloads]
                log(f"served {alg}: window {w} {label}={secs:.3f} "
                    f"(host wall clock, {len(window)} queries) "
                    f"iterations={iters}")
                answers[alg].update(zip(window, payloads))
        st = srv.stats("kron")
        log(f"served: scheduler dispatched={st['scheduler']['dispatched']} "
            f"batches={st['batches']} served={st['served']}")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device memory: peak_bytes_in_use={stats['peak_bytes_in_use']} "
            f"bytes_limit={stats.get('bytes_limit', 'not reported')}")
    else:
        log("device memory: peak not reported by this backend")
    return answers, tenant


def check_served(g, roots, answers, weight_seed: int, alpha: float) -> None:
    import numpy as np

    for alg in N_QUERIES:
        picked = roots[alg][:: max(1, len(roots[alg]) // N_CHECKED)]
        picked = picked[:N_CHECKED]
        t0 = time.perf_counter()
        if alg == "bfs":
            ref = bfs_reference(g, picked)
            for i, r in enumerate(picked):
                got = np.asarray(answers[alg][r]["levels"], np.int64)
                bad = int(np.sum(got != ref[i]))
                assert bad == 0, f"bfs root {r}: {bad} levels differ"
            detail = "levels equal"
        elif alg == "sssp":
            ref = sssp_reference(g, picked, weight_seed)
            for i, r in enumerate(picked):
                got = np.asarray(answers[alg][r]["dist"], np.float64)
                bad = int(np.sum(got != ref[i]))
                assert bad == 0, f"sssp root {r}: {bad} distances differ"
            detail = "distances equal"
        else:
            worst = 0.0
            for r in picked:
                p = answers[alg][r]
                ref = ppr_reference(g, r, alpha, int(p["iterations"]))
                err = float(np.max(np.abs(np.asarray(p["rank"], np.float64)
                                          - ref)))
                worst = max(worst, err)
                assert err <= PPR_ATOL, f"ppr root {r}: max error {err}"
            detail = f"max abs error {worst:.3e} <= {PPR_ATOL:g}"
        log(f"reference {alg}: roots={picked} {detail} "
            f"(scipy, {time.perf_counter() - t0:.1f}s)")


# ---------------------------------------------------------------- kernels
def kernel_phase(seed: int) -> None:
    """Each Pallas graph kernel once on the chip, against its jnp oracle,
    at 128x128 tiles on integer-valued data (every semiring exact)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import build_bsr_padded, build_sell, frontier_from_dense
    from repro.core.semiring import BOOL_OR_AND, MIN_PLUS, PLUS_AND, PLUS_TIMES
    from repro.kernels import ops

    assert not ops.interpret_mode(), "kernels.ops would run in interpret mode"
    rng = np.random.default_rng(seed)
    n, tile, per_row = 8192, 128, 4
    nb = n // tile
    rows, cols = [], []
    for i in range(nb):               # 4 random tile columns per block row
        for tc in rng.choice(nb, per_row, replace=False):
            r, c = np.nonzero(rng.random((tile, tile)) < 0.05)
            rows.append(r + i * tile)
            cols.append(c + tc * tile)
    rows = np.concatenate(rows).astype(np.int32)
    cols = np.concatenate(cols).astype(np.int32)

    def run(name, fn, want, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text(), \
            f"{name}: no Mosaic kernel in the compiled program"
        got = np.asarray(compiled(*args)).reshape(np.shape(want))
        bad = int(np.sum(got != np.asarray(want)))
        assert bad == 0, f"{name}: {bad} entries differ from the oracle"
        return name

    done = []
    for sr in (PLUS_TIMES, MIN_PLUS, BOOL_OR_AND):
        dt = np.dtype(sr.dtype)
        vals = (np.ones(rows.size) if sr is BOOL_OR_AND
                else rng.integers(1, 10, rows.size)).astype(dt)
        live = rng.random(n) < 0.1
        x = np.where(live, rng.integers(1, 10, n), 0).astype(dt)
        if sr is BOOL_OR_AND:
            x = live.astype(dt)
        if sr is MIN_PLUS:
            x = np.where(live, x, np.inf).astype(dt)
        a = build_bsr_padded(rows, cols, vals, (n, n), sr, block=(tile, tile))
        s = build_sell(rows, cols, vals, (n, n), sr, block=(tile, tile), c=8)
        xj = jnp.asarray(x)
        f = frontier_from_dense(xj, sr)
        mv = ops.semiring_spmv_ref(a, xj, sr)
        msv = ops.semiring_spmspv_ref(a, f, sr)
        name = sr.name
        done += [
            run(f"spmv/{name}", lambda a, x: ops.semiring_spmv(a, x, sr),
                mv, a, xj),
            run(f"spmv_fused/{name}",
                lambda a, x: ops.semiring_spmv_fused(a, x, sr), mv, a, xj),
            run(f"spmv_fused_chunks4/{name}",
                lambda a, x: ops.semiring_spmv_fused(a, x, sr, chunks=4),
                mv, a, xj),
            run(f"spmv_sell/{name}",
                lambda s, x: ops.semiring_spmv_sliced(s, x, sr), mv, s, xj),
            run(f"spmspv/{name}",
                lambda a, f: ops.semiring_spmspv(a, f, sr), msv, a, f),
            run(f"spmspv_fused/{name}",
                lambda a, f: ops.semiring_spmspv_fused(a, f, sr), msv, a, f),
        ]
    for sr in (PLUS_TIMES, MIN_PLUS, BOOL_OR_AND, PLUS_AND):
        dt = np.dtype(sr.dtype)
        vals = (rng.integers(1, 10, rows.size) if sr in (PLUS_TIMES, MIN_PLUS)
                else np.ones(rows.size)).astype(dt)
        a = build_bsr_padded(rows, cols, vals, (n, n), sr, block=(tile, tile))
        b = (rng.integers(0, 10, (n, 256)) if sr in (PLUS_TIMES, MIN_PLUS)
             else rng.integers(0, 2, (n, 256))).astype(dt)
        mask = np.where(rng.random((n, 256)) < 0.3, sr.one, sr.zero
                        ).astype(dt)
        bj, mj = jnp.asarray(b), jnp.asarray(mask)
        want = ops.semiring_spgemm_ref(a, bj, sr, mask=mj)
        done.append(run(
            f"spgemm/{sr.name}",
            lambda a, b, m: ops.semiring_spgemm(a, b, sr, mask=m),
            want, a, bj, mj))
    log(f"kernels: {len(done)} Pallas runs equal their oracles exactly, "
        f"each compiled to tpu_custom_call: {', '.join(done)}")


# ------------------------------------------------------------ four chips
def _per_device_bytes(tree) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(out.items()))


def four_chip_phase(g, roots) -> None:
    """The served BFS flush with its query block sharded over 4 devices,
    and the Fig.-3 partitioned SpMV on a 2x2 grid (its Merge phase is the
    collective), each element-exact against one device on the same graph."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.semiring import BOOL_OR_AND, MIN_PLUS
    from repro.graphs.engine import build_engine
    from repro.graphs.multi import bfs_multi
    from repro.launch.mesh import make_mesh
    from repro.serve.graph_engine import GraphQueryServer

    assert len(jax.devices()) == 4, jax.devices()
    batch_mesh = make_mesh((4,), ("batch",))
    srv = GraphQueryServer(g, batch_size=BATCH, mesh=batch_mesh)
    sources = roots["bfs"][:BATCH]
    reqs = [srv.submit("bfs", r) for r in sources]
    t0 = time.perf_counter()
    srv.flush()
    log(f"four-chip served bfs: sharded flush_s="
        f"{time.perf_counter() - t0:.3f} (host wall clock)")
    eng = srv.engine("bfs")
    sharded = bfs_multi(eng, sources, max_iters=srv.max_iters,
                        policy=srv.policy, mesh=batch_mesh)
    log(f"four-chip served bfs: levels [B, n] bytes per device "
        f"{_per_device_bytes(sharded.levels)}")
    # the same engine and runner, the query block on one device
    single = bfs_multi(eng, sources, max_iters=srv.max_iters,
                       policy=srv.policy)
    for i, req in enumerate(reqs):
        np.testing.assert_array_equal(req.result["levels"],
                                      np.asarray(single.levels[i]))
        assert req.result["iterations"] == int(single.iterations[i])
    log(f"four-chip served bfs: {BATCH} queries element-exact against one "
        f"device")

    grid_mesh = make_mesh((2, 2), ("dr", "dc"))
    flat = NamedSharding(grid_mesh, P(("dr", "dc")))
    part = GraphQueryServer(g, partition_devices=4, strategy="2d",
                            weight_seed=srv.weight_seed)
    one = {"bfs": eng,
           # the partitioned path keys SSSP weights by edge position
           "sssp": build_engine(g, MIN_PLUS, weighted=True,
                                seed=srv.weight_seed)}
    rng = np.random.default_rng(1)
    for alg, sr in (("bfs", BOOL_OR_AND), ("sssp", MIN_PLUS)):
        t0 = time.perf_counter()
        pm, fn, choice = part.partitioned_matvec(alg, grid_mesh,
                                                 kernel="spmv")
        live = rng.random(g.n) < 0.05
        if sr is BOOL_OR_AND:
            x, fill = live.astype(np.int32), 0
        else:
            x = np.where(live, rng.integers(0, 10, g.n), np.inf
                         ).astype(np.float32)
            fill = np.inf
        parts = jax.device_put(pm.parts, flat)
        xs = jax.device_put(jnp.asarray(
            pm.plan.shard_input_vector(x, fill), sr.dtype), flat)
        tag = f"four-chip partitioned {alg}"
        log(f"{tag}: strategy={choice.strategy} balance={choice.balance} "
            f"merge={choice.merge} set-up_s={time.perf_counter() - t0:.3f}; "
            f"matrix bytes per device {_per_device_bytes(parts)}")
        y_sh = jax.jit(fn)(parts, xs)
        y = pm.plan.unshard_output_vector(np.asarray(y_sh))[: g.n]
        want = np.asarray(one[alg].spmv_fn(jnp.asarray(x, sr.dtype)))
        np.testing.assert_array_equal(y, want[: g.n])
        log(f"{tag}: output bytes per device {_per_device_bytes(y_sh)}; "
            f"SpMV element-exact against one device")


# ------------------------------------------------------------------- main
def _phase(name: str, fn, *args, failures: list):
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    except Exception:
        failures.append(name)
        log(f"PHASE FAILED: {name}\n{traceback.format_exc()}")
        return None
    log(f"phase {name}: ok ({time.perf_counter() - t0:.1f}s)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=21,
                    help="Kronecker scale S: 2**S vertices (>= 20)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device mesh paths")
    args = ap.parse_args(argv)
    if args.scale < 20:
        ap.error("--scale must be >= 20")
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository sources at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # the TPU runtime would otherwise log to a directory outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this run needs one",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    failures: list = []
    graph = _phase("graph", build_graph, args.scale, args.seed,
                   failures=failures)
    if graph is not None:
        g, roots = graph
        if args.four_chips:
            _phase("four_chips", four_chip_phase, g, roots,
                   failures=failures)
        else:
            _phase("kernels", kernel_phase, args.seed, failures=failures)
            served = _phase("served", served_phase, g, roots,
                            failures=failures)
            if served is not None:
                answers, tenant = served
                _phase("references", check_served, g, roots, answers,
                       tenant.weight_seed, tenant.alpha, failures=failures)
    if failures:
        print(f"chip_smoke: failed phases: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

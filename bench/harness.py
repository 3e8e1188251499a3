"""One run of one cell: graph from the seed, the served path set up and
warmed, an open loop at the cell's rate for the measured window, then the
comparison with the reference and the metrics.

The system under test is the program's ``AsyncGraphServer``: the loop calls
``submit`` and ``QueryTicket.wait`` exactly as users do. Everything else
(graphs, traffic, references, the reduction of counters and traces to
metrics) is the benchmark's own.
"""
from __future__ import annotations

import gc
import os
import queue
import shutil
import sys
import threading
import time

import numpy as np

from bench import devtrace, graphgen, spec, traffic

# Queries per answer sample drawn from the seed for the comparison.
CHECK_SAMPLE = 48
# How long past the window's close an answer is waited for before it
# counts as never coming.
LATE_S = 60.0
# JAX's event for tracing a new program (each compile, or cache hit, starts
# with one).
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU found (JAX platform "
                            f"{devices[0].platform!r}); this run needs one")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} TPU chips, JAX found "
                            f"{len(devices)}")
    return devices


def set_compile_cache(root):
    """JAX_COMPILATION_CACHE_DIR when set, else ``.jax_cache`` at the root
    of the checkout (a fixed path: the path is part of the cache key)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class OpenLoop:
    """Sends each stream item at its due time, whether or not earlier ones
    have been answered, and waits for the answers on a second thread."""

    def __init__(self, server, tenant: str, stream):
        self.server, self.tenant, self.stream = server, tenant, stream
        self.records = []

    def _send(self, t0: float, outbox: queue.SimpleQueue):
        from jax.profiler import TraceAnnotation

        for i, (due, alg, root) in enumerate(self.stream):
            delay = t0 + due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            rec = {"i": i, "alg": alg, "root": root, "ok": False,
                   "t_due": due, "t_sent": time.perf_counter() - t0}
            try:
                with TraceAnnotation("bench/submit"):
                    rec["ticket"] = self.server.submit(self.tenant, alg, root)
            except Exception as e:  # a refused query is recorded, not lost
                rec["error"] = f"{type(e).__name__}: {e}"
            self.records.append(rec)
            outbox.put(rec)
        outbox.put(None)

    def _wait(self, t0: float, seconds: float, outbox: queue.SimpleQueue):
        """Answers come in submission order (windows drain in order), so
        one waiter sees each within microseconds of its resolution."""
        from jax.profiler import TraceAnnotation

        while (rec := outbox.get()) is not None:
            if "ticket" in rec:
                try:
                    with TraceAnnotation(devtrace.CLIENT_WAIT):
                        rec["payload"] = rec["ticket"].wait(timeout=max(
                            0.0, t0 + seconds + LATE_S - time.perf_counter()))
                    rec["ok"] = True
                except Exception as e:  # a failed or late answer
                    rec["error"] = f"{type(e).__name__}: {e}"
            rec["t_done"] = time.perf_counter() - t0

    def run(self, seconds: float, on_start=None, on_deadline=None) -> float:
        """Runs the window; returns its start on the host clock. Blocks
        until every query sent has its answer or has given up."""
        from jax.profiler import TraceAnnotation

        outbox = queue.SimpleQueue()
        if on_start is not None:
            on_start()
        with TraceAnnotation(devtrace.WINDOW):
            t0 = time.perf_counter()
            sender = threading.Thread(target=self._send, args=(t0, outbox),
                                      name="bench-sender", daemon=True)
            waiter = threading.Thread(target=self._wait,
                                      args=(t0, seconds, outbox),
                                      name="bench-waiter", daemon=True)
            sender.start()
            waiter.start()
            time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        if on_deadline is not None:
            on_deadline()
        for t in (sender, waiter):
            t.join(timeout=seconds + LATE_S + 30.0)
            if t.is_alive():
                raise RuntimeError(f"{t.name} still running")
        return t0


def _stats(server, tenant: str) -> dict:
    st = server.stats(tenant)
    return {"batches": st["batches"], "served": st["served"],
            "cache_hits": st["cache_hits"], "deduped": st["deduped"],
            "lookups": st["cache"]["lookups"], "hits": st["cache"]["hits"],
            "window_occupancy": st["latency"].get("window_occupancy",
                                                  {"count": 0})}


def _buckets(records, batch: int) -> list:
    """The device buckets of the window, rebuilt from the tickets the way
    the flush forms them: per scheduler window, per algorithm in ticket
    order, the distinct roots that missed the cache, in chunks of
    ``batch``."""
    windows = {}
    for r in records:
        tk = r.get("ticket")
        if r["ok"] and tk is not None and not tk.cached:
            windows.setdefault(tk.window_id, []).append((tk.seq, r))
    out = []
    for wid in sorted(windows):
        by_alg = {}
        for _seq, r in sorted(windows[wid], key=lambda x: x[0]):
            seen = by_alg.setdefault(r["alg"], {})
            seen.setdefault(r["root"], r)
        for alg, firsts in by_alg.items():
            rows = list(firsts.values())
            for lo in range(0, len(rows), batch):
                chunk = rows[lo:lo + batch]
                out.append({"alg": alg, "roots": [r["root"] for r in chunk],
                            "trips": max(r["payload"]["iterations"]
                                         for r in chunk),
                            "records": chunk})
    return out


def _bucket_traffic(buckets, rows, cols, n, degrees) -> list:
    """Per bucket: algorithm, rows, loop trips and, per trip, the stored
    entries a push would read (its algorithm's ``frontier_entries``, see
    bench/roofline.py)."""
    out = []
    for b in buckets:
        alg = spec.algorithm(b["alg"])
        payloads = [r["payload"] for r in b["records"]]
        out.append({"alg": b["alg"], "rows": len(b["roots"]),
                    "trips": b["trips"],
                    "frontier_entries": alg.frontier_entries(
                        payloads, rows, cols, n, b["roots"], degrees,
                        b["trips"])})
    return out


def compare(records, rows, cols, n, seed: int, control: bool = False) -> dict:
    """The comparison that decides ``correct``: every query sent must be
    answered, and a sample of the answers drawn from ``seed`` (which also
    keys any edge weights), per algorithm, must pass that algorithm's
    ``count_wrong`` against its reference (bench/algorithms/<alg>.py). With
    ``control`` the reference's control answers stand in for the
    program's."""
    checks = {"unanswered": {"value": sum(not r["ok"] for r in records),
                             "limit": 0}}
    rng = np.random.default_rng([seed, 0xC4EC])
    answered = [r for r in records if r["ok"]]
    for name in sorted({r["alg"] for r in records}):
        alg = spec.algorithm(name)
        distance = getattr(alg, "distance", None)
        mine = [r for r in answered if r["alg"] == name]
        if len(mine) > CHECK_SAMPLE:
            pick = rng.choice(len(mine), CHECK_SAMPLE, replace=False)
            mine = [mine[i] for i in sorted(pick)]
        roots = sorted({r["root"] for r in mine})
        wrong, worst = 0, 0.0
        if roots:
            want = alg.answers(rows, cols, n, roots, seed)
            if control:
                got_by_root = dict(zip(roots, alg.control(
                    rows, cols, n, roots, seed, want)))
            row_of = {root: i for i, root in enumerate(roots)}
            for r in mine:
                got = (got_by_root[r["root"]] if control else
                       r["payload"][alg.PAYLOAD_FIELD])
                ref = want[row_of[r["root"]]]
                wrong += alg.count_wrong(got, ref)
                if distance is not None:
                    worst = max(worst, distance(got, ref))
        checks[f"{name}_wrong"] = {"value": wrong, "limit": 0}
        if distance is not None:
            checks[f"{name}_{alg.DISTANCE}"] = {"value": worst,
                                                "limit": alg.LIMIT}
        checks[f"{name}_checked"] = {"value": len(mine), "limit": None}
    return checks


def _quantile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(records, seconds: float, setup_s: float) -> dict:
    """Client-side metrics. Latency runs from each query's due time to its
    answer, over every query sent in the window (those answered after its
    close included). The rate counts the answers inside the window, which
    closes at the last of them."""
    out = {"setup_s": setup_s}
    answered = [r for r in records if r["ok"]]
    inside = [r["t_done"] for r in answered if r["t_done"] <= seconds]
    if inside:
        out["queries_per_s"] = len(inside) / max(inside)
    if answered:
        lat = [r["t_done"] - r["t_due"] for r in answered]
        out["latency_p50_s"] = _quantile(lat, 50)
        out["latency_p90_s"] = _quantile(lat, 90)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root=spec.ROOT, require_tpu: bool = True,
             config_override: dict | None = None,
             traffic_override: dict | None = None,
             control: bool = False) -> dict:
    """One run; returns the result dict (its ``checks`` key last). With
    ``control`` the control's answers stand in for the program's in the
    comparison, so ``correct`` is the control's (see :func:`compare`)."""
    c = spec.cell(workload, root)
    w = c["workload"]
    cfg = {**c["config"], **(config_override or {})}
    tf = {**c["traffic"], **(traffic_override or {})}
    chips = int(w["chips"])
    import jax

    if require_tpu:
        devices = check_devices(chips)
        set_compile_cache(root)
    else:
        devices = jax.devices()
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    from repro.graphs.datasets import Graph
    from repro.serve.graph_engine import AsyncGraphServer

    rows, cols, n, perm = graphgen.generate(cfg, seed)
    degrees = np.bincount(rows, minlength=n)
    warm, stream = traffic.make(tf, degrees, perm, seed, seconds)
    batch = int(tf["batch"])
    kwargs = {"batch_size": batch, "weight_seed": seed}
    if chips > 1:
        from repro.launch.mesh import make_mesh

        kwargs["mesh"] = make_mesh((chips,), ("batch",),
                                   devices=devices[:chips])
    name = w["config"]
    server = AsyncGraphServer(max_wait=float(tf["max_wait_s"]))
    loop = None
    try:
        server.start()
        tenant = server.add_tenant(name, Graph(rows, cols, n, name=name),
                                   **kwargs)
        for alg in sorted(tf["mix"]):
            jax.block_until_ready(tenant.engine(alg).mats)
        for alg, roots in sorted(warm.items()):
            for tk in [server.submit(name, alg, r) for r in roots]:
                tk.wait(timeout=1200.0)
        setup_s = time.perf_counter() - t_start
        before = _stats(server, name)
        loop = OpenLoop(server, name, stream)
        trace_dir = devtrace.capture_dir(str(root), workload, seed)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        traced = []

        def count_traces(event, _secs, **_kw):
            if event == TRACE_EVENT:
                traced.append(event)

        jax.monitoring.register_event_duration_secs_listener(count_traces)
        try:
            loop.run(seconds,
                     on_start=(lambda: jax.profiler.start_trace(
                         trace_dir, profiler_options=opts)) if trace else None,
                     on_deadline=jax.profiler.stop_trace if trace else None)
        finally:
            jax.monitoring.unregister_event_duration_listener(count_traces)
        after = _stats(server, name)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:chips])
    finally:
        server.close()
    records = loop.records
    del tenant, server
    gc.collect()

    record = {"seconds": seconds, "batch": batch, "chips": chips,
              "n": n, "nnz": int(rows.shape[0]),
              "stats_before": before, "stats_after": after}
    result_device = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": len(devices),
                     "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace:
        pending = [(r["t_sent"], r["t_done"]) for r in records]
        reduced = devtrace.reduce(devtrace.load(trace_dir), chips, pending)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is not None:
            for key in ("busy_s", "window_s", "pending_s", "busy_pending_s"):
                record[key] = reduced[key]
            record["peaks"] = spec.peaks(devices[0].device_kind)
            result_device["busy_s"] = reduced["busy_s"]
            result_device["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]
        # the buckets whose answers all came inside the traced window, the
        # one whose device time busy_s counts
        record["buckets"] = _bucket_traffic(
            [b for b in _buckets(records, batch)
             if all(r["t_done"] <= seconds for r in b["records"])],
            rows, cols, n, degrees)

    checks = compare(records, rows, cols, n, seed, control=control)
    correct = all(v["value"] <= v["limit"] for v in checks.values()
                  if v["limit"] is not None)
    if trace:
        metrics = {}
        for m in c["per_layer"]:
            value = spec.metric_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = end_to_end(records, seconds, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"] if m["name"] in e2e}
    out = {"correct": correct, "attempted": len(records),
           "failed": sum(not r["ok"] for r in records),
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    # every program the window runs was traced and compiled in set-up
    out["programs_traced_in_window"] = len(traced)
    out["sender_late_s_max"] = max((r["t_sent"] - r["t_due"] for r in records),
                                   default=0.0)
    out["answered_in_window"] = sum(r["ok"] and r["t_done"] <= seconds
                                    for r in records)
    # loop trips of each device bucket the window ran, in order
    out["bucket_trips"] = [b["trips"] for b in _buckets(records, batch)]
    out["checks"] = checks
    return out


def print_checks(checks: dict) -> None:
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)

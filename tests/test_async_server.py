"""AsyncGraphServer: the event-loop serving layer.

Four suites in one file, all pinned against the synchronous
GraphQueryServer as the oracle:

* **differential** — identical seeded workloads (mixed traversal +
  whole-graph kinds, a live ``mutate()`` in the middle) replayed through
  the async server (fake clock, windows flushing at arbitrary points)
  and the synchronous server (one flush per phase). Payloads must be
  **element-exact** equal: batched rows are computed independently and
  frozen at convergence, so bucket composition can never leak into
  answers.
* **fake-clock scheduling** — time-window expiry, bucket-fill flush,
  deadline-pulled early flush, EDF dispatch order, mutation
  interleaving (queued queries observe the pre-mutation snapshot), and
  multi-tenant isolation over the shared LRU.
* **backpressure** — saturating admission raises the typed
  BackpressureError (never a silent drop), the rejection is counted in
  the tenant's ``stats()["latency"]``, and queue depth never exceeds
  the bound.
* **flush edge semantics** — flushing an empty queue is a free no-op
  (no metrics skew) and an already-resolved request passes through a
  second flush untouched; ticket re-resolution is a no-op returning the
  cached payload.

Plus a threaded stress run (``slow`` marker; watchdogged by
pytest-timeout in CI): concurrent submitters on two tenants with a
mutator and a stats sampler — no lost or duplicated responses, and the
shared LRU's ``hits + misses == lookups`` invariant holds in every
mid-flight snapshot, not just at quiescence.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.delta import EdgeDelta
from repro.graphs import generate
from repro.serve.graph_engine import (
    GLOBAL_ALGORITHMS, AsyncGraphServer, GraphQueryServer,
)
from repro.serve.scheduler import (
    BackpressureError, FakeClock, QueryTicket, WindowScheduler, _edf_key,
)


@pytest.fixture(scope="module")
def graph():
    return generate("face", scale=0.15, seed=1)


def assert_payload_equal(got, want, label=""):
    """Element-exact payload equality (arrays bitwise, scalars ==)."""
    assert got is not None and want is not None, f"unresolved: {label}"
    assert set(got) == set(want), f"{label}: keys {set(got)} != {set(want)}"
    for k in want:
        g, w = got[k], want[k]
        if isinstance(w, np.ndarray) or isinstance(g, np.ndarray):
            np.testing.assert_array_equal(g, w, err_msg=f"{label}[{k}]")
        else:
            assert g == w, f"{label}[{k}]: {g} != {w}"


# ---------------------------------------------------------------------------
# differential oracle: async (windowed, fake clock) vs sync (explicit flush)
# ---------------------------------------------------------------------------

def _random_queries(rng, n, k):
    algs = ("bfs", "sssp", "ppr", "cc", "pagerank")
    out = []
    for _ in range(k):
        a = algs[int(rng.integers(0, len(algs)))]
        s = None if a in GLOBAL_ALGORITHMS else int(rng.integers(0, n))
        out.append((a, s))
    return out


def _random_delta(rng, g, k=3):
    ir = rng.integers(0, g.n, k)
    ic = (ir + 1 + rng.integers(0, g.n - 1, k)) % g.n   # never a self-loop
    idx = rng.integers(0, len(g.rows), 2)
    return EdgeDelta(insert_rows=ir, insert_cols=ic,
                     delete_rows=np.asarray(g.rows)[idx],
                     delete_cols=np.asarray(g.cols)[idx])


@pytest.mark.parametrize("pipeline_depth", [0, 2])
@pytest.mark.parametrize("strategy", ["auto", "col"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_differential_matches_sync_server(seed, strategy, pipeline_depth):
    g = generate("face", scale=0.15, seed=seed)
    clock = FakeClock()
    asrv = AsyncGraphServer(clock=clock, max_pending=1024, max_wait=0.05)
    asrv.add_tenant("t", g, batch_size=4, pipeline_depth=pipeline_depth,
                    strategy=strategy)
    ssrv = GraphQueryServer(g, batch_size=4, pipeline_depth=pipeline_depth,
                            strategy=strategy)

    rng = np.random.default_rng(100 + seed)
    pairs = []

    def run_phase(queries):
        for a, s in queries:
            dl = (float(rng.uniform(0.005, 0.1))
                  if rng.random() < 0.3 else None)
            pr = int(rng.integers(0, 3))
            pairs.append((asrv.submit("t", a, s, deadline=dl, priority=pr),
                          ssrv.submit(a, s)))
            # windows flush at arbitrary interior points for the async
            # server; the sync oracle flushes once per phase — bucket
            # composition must not matter
            if rng.random() < 0.25:
                clock.advance(float(rng.uniform(0.0, 0.08)))
                asrv.poll()
        asrv.drain()
        ssrv.flush()

    run_phase(_random_queries(rng, g.n, 10))

    delta = _random_delta(rng, asrv.tenant("t").graph)
    ra = asrv.mutate("t", delta)
    rs = ssrv.mutate(delta)
    assert (ra["version"], ra["inserted"], ra["deleted"]) == \
        (rs["version"], rs["inserted"], rs["deleted"])

    run_phase(_random_queries(rng, g.n, 8))

    for i, (tk, req) in enumerate(pairs):
        assert tk.done()
        assert_payload_equal(tk.result, req.result,
                             label=f"q{i}:{tk.algorithm}/{tk.source}")


def test_differential_across_mutate_epochs_cache_retention(graph):
    """A repeated far-away source must be answerable from the migrated
    cache after a local delta — and still equal the sync oracle."""
    clock = FakeClock()
    asrv = AsyncGraphServer(clock=clock, max_pending=64, max_wait=0.02)
    asrv.add_tenant("t", graph, batch_size=4)
    ssrv = GraphQueryServer(graph, batch_size=4)

    src = int(graph.n // 3)
    t1 = asrv.submit("t", "bfs", src)
    r1 = ssrv.submit("bfs", src)
    asrv.drain(); ssrv.flush()
    assert_payload_equal(t1.result, r1.result)

    # a delta confined to vertices the cached answer provably cannot
    # reach keeps the entry live across the epoch... or invalidates it
    # in both servers identically; either way answers must agree.
    delta = _random_delta(np.random.default_rng(9),
                          asrv.tenant("t").graph, k=2)
    asrv.mutate("t", delta)
    ssrv.mutate(delta)
    t2 = asrv.submit("t", "bfs", src)
    r2 = ssrv.submit("bfs", src)
    asrv.drain(); ssrv.flush()
    assert_payload_equal(t2.result, r2.result)
    assert t2.cached == r2.cached


# ---------------------------------------------------------------------------
# fake-clock window scheduling
# ---------------------------------------------------------------------------

def test_time_window_flush(graph):
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_wait=0.05)
    srv.add_tenant("t", graph, batch_size=8)
    tks = [srv.submit("t", "bfs", s) for s in (0, 1)]
    assert srv.poll() == 0                      # window not due yet
    clock.advance(0.049)
    assert srv.poll() == 0                      # still inside the budget
    clock.advance(0.002)
    assert srv.poll() == 2                      # budget expired -> flush
    assert all(t.done() for t in tks)


def test_fill_flush_is_immediate(graph):
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_wait=10.0)
    srv.add_tenant("t", graph, batch_size=4)
    tks = [srv.submit("t", "bfs", s) for s in range(4)]
    assert srv.poll() == 4                      # bucket full: due at once
    assert all(t.done() for t in tks)
    occ = srv.stats("t")["latency"]["window_occupancy"]
    assert occ["count"] == 1 and occ["max"] == pytest.approx(1.0)


def test_deadline_pulls_flush_early(graph):
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_wait=0.05)
    srv.add_tenant("t", graph, batch_size=8)
    srv.submit("t", "bfs", 0)
    tk = srv.submit("t", "bfs", 1, deadline=0.01)   # pulls expiry earlier
    clock.advance(0.011)
    assert srv.poll() == 2 and tk.done()
    # the deadline ordered dispatch too: earliest deadline first
    assert tk.dispatched_at == pytest.approx(0.011)


def test_edf_dispatch_order():
    """EDF within a window, engine-free: earliest deadline first, ties by
    priority (higher first) then admission order."""
    batches = []
    clock = FakeClock()
    sched = WindowScheduler(lambda name, tks: batches.append(tks),
                            clock=clock, max_pending=64)
    sched.register("t", batch_size=16, max_wait=1.0)
    specs = [(None, 0), (0.5, 0), (0.1, 0), (None, 2), (0.1, 1)]
    for dl, pr in specs:
        sched.submit(QueryTicket("t", "bfs", 0, priority=pr, deadline=dl))
    sched.drain()
    (tks,) = batches
    assert [(t.deadline, t.priority) for t in tks] == \
        [(0.1, 1), (0.1, 0), (0.5, 0), (None, 2), (None, 0)]
    keys = [_edf_key(t) for t in tks]
    assert keys == sorted(keys)


def test_executor_error_fails_its_window_only():
    """A window whose executor raises resolves its tickets with the error
    (wait() raises it, nothing blocks); other windows still run, and
    raise_failure() re-raises the error once."""
    clock = FakeClock()
    served = []

    def executor(name, tks):
        if name == "bad":
            raise MemoryError("device out of memory")
        for tk in tks:
            tk.resolve({"ok": True})
        served.append(name)

    sched = WindowScheduler(executor, clock=clock, max_pending=64)
    sched.register("bad", batch_size=2, max_wait=1.0)
    sched.register("good", batch_size=2, max_wait=1.0)
    bad = [sched.submit(QueryTicket("bad", "bfs", s)) for s in range(2)]
    good = sched.submit(QueryTicket("good", "bfs", 0))
    clock.advance(1.0)
    assert sched.poll() == 3
    assert served == ["good"] and good.wait(timeout=0) == {"ok": True}
    for tk in bad:
        assert tk.done()
        with pytest.raises(MemoryError, match="out of memory"):
            tk.wait(timeout=0)
    assert sched.stats()["dispatched"] == 3
    with pytest.raises(MemoryError):
        sched.raise_failure()
    sched.raise_failure()                 # raised once, then cleared


def test_close_reraises_flush_error(graph, monkeypatch):
    """A flush that raises inside the serving loop fails its tickets and
    makes close() raise, instead of leaving waiters blocked forever."""
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_wait=0.01)
    server = srv.add_tenant("t", graph, batch_size=4)

    def broken_flush():
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(server, "flush", broken_flush)
    tks = [srv.submit("t", "bfs", s) for s in range(4)]
    srv.poll()
    for tk in tks:
        with pytest.raises(RuntimeError, match="refused"):
            tk.wait(timeout=0)
    with pytest.raises(RuntimeError, match="refused"):
        srv.close()


def test_mutate_interleaves_with_pending_window(graph):
    """Queries queued before mutate() observe the pre-mutation snapshot;
    queries after observe the new one — async matches sync exactly."""
    clock = FakeClock()
    asrv = AsyncGraphServer(clock=clock, max_wait=10.0)
    asrv.add_tenant("t", graph, batch_size=64)      # nothing auto-flushes
    oracle_pre = GraphQueryServer(graph, batch_size=64)

    src = 3
    tk_pre = asrv.submit("t", "bfs", src)
    delta = EdgeDelta(insert_rows=[src], insert_cols=[src + 1])
    report = asrv.mutate("t", delta)                # drains the window first
    assert tk_pre.done() and report["version"] == 1

    r_pre = oracle_pre.submit("bfs", src)
    oracle_pre.flush()
    assert_payload_equal(tk_pre.result, r_pre.result, label="pre-mutation")

    tk_post = asrv.submit("t", "bfs", src)
    asrv.drain()
    oracle_post = GraphQueryServer(asrv.tenant("t").graph, batch_size=64)
    r_post = oracle_post.submit("bfs", src)
    oracle_post.flush()
    assert_payload_equal(tk_post.result, r_post.result, label="post-mutation")


def test_multi_tenant_shared_cache_and_isolated_stats():
    ga = generate("face", scale=0.15, seed=1)
    gb = generate("face", scale=0.15, seed=7)
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_wait=10.0, cache_capacity=64)
    sa = srv.add_tenant("a", ga, batch_size=4)
    sb = srv.add_tenant("b", gb, batch_size=4)
    # one LRU = the multi-tenant memory budget
    assert sa.cache is srv.cache and sb.cache is srv.cache
    # distinct graphs -> distinct engine fingerprints -> no key collisions
    assert sa.engine_key != sb.engine_key

    ta = [srv.submit("a", "bfs", s) for s in range(4)]
    tb = [srv.submit("b", "bfs", s) for s in range(2)]
    srv.drain()
    assert all(t.done() for t in ta + tb)

    st_a, st_b = srv.stats("a"), srv.stats("b")
    assert st_a["served"] == 4 and st_b["served"] == 2     # per-tenant
    assert st_a["cache"] == st_b["cache"]                   # shared budget
    assert st_a["cache"]["size"] == 6
    assert st_a["scheduler"]["dispatched"] == 6

    # a re-ask on each tenant hits only its own entries
    t2 = srv.submit("a", "bfs", 0)
    srv.drain()
    assert t2.done() and t2.cached
    np.testing.assert_array_equal(t2.result["levels"], ta[0].result["levels"])


def test_submit_validates_eagerly(graph):
    srv = AsyncGraphServer(clock=FakeClock())
    srv.add_tenant("t", graph)
    with pytest.raises(ValueError):
        srv.submit("t", "bfs")                  # traversal needs a source
    with pytest.raises(ValueError):
        srv.submit("t", "cc", 0)                # global takes none
    with pytest.raises(ValueError):
        srv.submit("t", "bfs", graph.n + 5)     # out of range
    with pytest.raises(ValueError):
        srv.submit("ghost", "bfs", 0)           # unknown tenant
    assert srv.scheduler.stats()["admitted"] == 0


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------

def test_backpressure_typed_and_counted(graph):
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_pending=8, max_wait=10.0)
    srv.add_tenant("t", graph, batch_size=64)   # window never self-flushes
    tks = [srv.submit("t", "bfs", s % graph.n) for s in range(8)]
    with pytest.raises(BackpressureError) as ei:
        srv.submit("t", "bfs", 0)
    err = ei.value
    assert (err.tenant, err.depth, err.max_pending) == ("t", 8, 8)

    st = srv.stats("t")
    assert st["latency"]["rejected"] == 1       # observable, per tenant
    sched = st["scheduler"]
    assert sched["rejected"] == 1 and sched["pending"] == 8
    assert sched["depth_high_water"] <= sched["max_pending"]

    # shedding never loses admitted work: a drain resolves all 8,
    # and admission reopens
    assert srv.drain() == 8 and all(t.done() for t in tks)
    tk = srv.submit("t", "bfs", 1)
    srv.drain()
    assert tk.done()


# ---------------------------------------------------------------------------
# flush edge semantics (the PR's pinned fixes)
# ---------------------------------------------------------------------------

def test_flush_empty_queue_is_free_noop(graph):
    srv = GraphQueryServer(graph, batch_size=4)
    assert srv.flush() == []
    st = srv.stats()
    assert st["served"] == 0 and st["batches"] == 0
    # an idle tick must not skew the latency accounting
    assert st["latency"]["queue_depth"]["writes"] == 0
    assert "flush_s" not in st["latency"]


def test_double_flush_of_resolved_request_is_untouched(graph):
    srv = GraphQueryServer(graph, batch_size=4)
    req = srv.submit("bfs", 2)
    srv.flush()
    payload = req.result
    assert payload is not None
    before = srv.stats()

    # the double-flush: the same (already resolved) request rides a later
    # queue alongside a fresh one
    srv._queue.append(req)
    fresh = srv.submit("bfs", 5)
    done = srv.flush()
    assert done == [req, fresh]
    assert req.result is payload                # untouched, not recomputed
    after = srv.stats()
    assert after["served"] == before["served"] + 1      # only the fresh one
    assert after["batches"] == before["batches"] + 1

    # and a queue of *only* resolved requests is a pure pass-through
    srv._queue.append(req)
    assert srv.flush() == [req]
    assert srv.stats()["served"] == after["served"]


def test_ticket_reresolution_is_noop():
    tk = QueryTicket("t", "bfs", 0)
    assert not tk.done()
    first = {"levels": np.arange(3)}
    assert tk.resolve(first) is first
    assert tk.resolve({"levels": np.zeros(3)}, cached=True) is first
    assert tk.result is first and tk.cached is False
    assert tk.wait(timeout=0) is first


def test_ticket_wait_times_out_unresolved():
    tk = QueryTicket("t", "bfs", 0)
    with pytest.raises(TimeoutError):
        tk.wait(timeout=0.01)


# ---------------------------------------------------------------------------
# SLO accounting: deadline misses, slack, abandonment (fake clock)
# ---------------------------------------------------------------------------

def test_slo_splits_latency_into_queue_wait_and_service(graph):
    """Admitted at 0, dispatched at 0.3, resolved at 1.0: 0.3 s of queue
    wait and 0.7 s of service, counted once (a second resolve of the
    same ticket adds nothing)."""
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_wait=0.3)
    tenant = srv.add_tenant("t", graph, batch_size=8)
    flush = tenant.flush

    def slow_flush():
        clock.advance(0.7)
        return flush()

    tenant.flush = slow_flush
    tk = srv.submit("t", "bfs", 0)
    clock.advance(0.3)
    assert srv.poll() == 1
    assert (tk.admitted_at, tk.dispatched_at, tk.resolved_at) == \
        pytest.approx((0.0, 0.3, 1.0))
    slo = srv.stats("t")["slo"]
    assert slo["queue_wait_s"] == pytest.approx(0.3)
    assert slo["service_s"] == pytest.approx(0.7)
    srv._drain_tenant("t", [tk])                # a duplicate drain
    again = srv.stats("t")["slo"]
    assert (again["queue_wait_s"], again["service_s"], again["resolved"]) \
        == (slo["queue_wait_s"], slo["service_s"], slo["resolved"])


def test_slo_deadline_miss_accounting(graph):
    """Misses are classified by signed slack at resolve time, counted
    exactly once, and conserved: goodput + misses + no-deadline ==
    resolved in every stats() snapshot."""
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_wait=0.05)
    srv.add_tenant("t", graph, batch_size=8)
    hit = srv.submit("t", "bfs", 0, deadline=10.0)
    miss = srv.submit("t", "bfs", 1, deadline=0.01)
    free = srv.submit("t", "bfs", 2)                # no deadline
    clock.advance(0.06)                             # past window + deadline
    assert srv.poll() == 3

    # slack sign convention: resolved after the deadline is negative
    assert hit.slack() == pytest.approx(10.0 - 0.06)
    assert miss.slack() == pytest.approx(0.01 - 0.06)
    assert free.slack() is None

    slo = srv.stats("t")["slo"]
    assert slo["resolved"] == 3
    assert (slo["goodput"], slo["deadline_misses"], slo["no_deadline"]) \
        == (1, 1, 1)
    assert slo["goodput"] + slo["deadline_misses"] + slo["no_deadline"] \
        == slo["resolved"] == slo["dispatched"]
    assert slo["admitted"] == slo["dispatched"] + slo["pending"] \
        + slo["abandoned"]
    # the slack histogram saw both deadlined tickets (signed), the
    # lateness histogram only the miss (positive lateness)
    assert slo["slack_s"]["count"] == 2
    assert slo["lateness_s"]["count"] == 1
    assert slo["lateness_s"]["min"] == pytest.approx(0.05)

    # counted exactly once: idle polls and re-reads never move anything
    srv.poll(); srv.drain()
    again = srv.stats("t")["slo"]
    for k in ("resolved", "goodput", "deadline_misses", "no_deadline"):
        assert again[k] == slo[k]

    # the request timeline is complete and ordered
    tl = miss.timeline()
    assert tl["request_id"] and tl["window_id"] >= 0
    assert tl["tenant"] == "t" and not tl["abandoned"]
    assert tl["admitted_at"] <= tl["dispatched_at"] <= tl["resolved_at"]


def test_ticket_abandonment_accounting(graph):
    """A wait() timeout abandons the queued ticket: it leaves the window,
    is never dispatched, and the per-tenant conservation closes with the
    abandoned term — admitted == dispatched + pending + abandoned."""
    clock = FakeClock()
    srv = AsyncGraphServer(clock=clock, max_wait=10.0)
    srv.add_tenant("t", graph, batch_size=64)       # nothing self-flushes
    gone = srv.submit("t", "bfs", 0)
    kept = srv.submit("t", "bfs", 1)
    with pytest.raises(TimeoutError):
        gone.wait(timeout=0.01)
    assert gone.abandoned and not gone.done()
    assert gone.timeline()["abandoned"]

    slo = srv.stats("t")["slo"]
    assert slo["abandoned"] == 1 and slo["wait_timeouts"] == 1
    assert slo["pending"] == 1 and slo["dispatched"] == 0
    assert slo["admitted"] == slo["dispatched"] + slo["pending"] \
        + slo["abandoned"] == 2

    # the drain dispatches only the survivor
    assert srv.drain() == 1
    assert kept.done() and not gone.done()
    slo = srv.stats("t")["slo"]
    assert slo["dispatched"] == 1 and slo["pending"] == 0
    assert slo["resolved"] == 1 and slo["no_deadline"] == 1

    # a second timed-out wait on the same ticket never double-counts
    with pytest.raises(TimeoutError):
        gone.wait(timeout=0)
    after = srv.stats("t")["slo"]
    assert after["wait_timeouts"] == 1 and after["abandoned"] == 1

    # a resolved ticket's wait is unaffected by the abandonment path
    assert kept.wait(timeout=0) is kept.result


# ---------------------------------------------------------------------------
# threaded stress: shared LRU + metrics under concurrency
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.timeout(300)
def test_threaded_stress_no_lost_or_torn_state():
    graphs = {"a": generate("face", scale=0.1, seed=1),
              "b": generate("face", scale=0.1, seed=7)}
    errors: list = []
    tickets: dict = {}
    stop = threading.Event()

    with AsyncGraphServer(max_pending=256, max_wait=0.005) as srv:
        for name, g in graphs.items():
            srv.add_tenant(name, g, batch_size=4)

        def submitter(tid):
            tenant = ("a", "b")[tid % 2]
            g = graphs[tenant]
            rng = np.random.default_rng(1000 + tid)
            got = []
            for _ in range(30):
                alg = ("bfs", "sssp")[int(rng.integers(0, 2))]
                src = int(rng.integers(0, g.n))
                try:
                    got.append(srv.submit(
                        tenant, alg, src,
                        deadline=float(rng.uniform(0.001, 0.02)),
                        priority=int(rng.integers(0, 3))))
                except BackpressureError:
                    time.sleep(0.001)           # closed-loop backoff
            tickets[tid] = got

        def mutator():
            rng = np.random.default_rng(77)
            n = graphs["a"].n
            for _ in range(3):
                time.sleep(0.02)
                ir = rng.integers(0, n, 2)
                ic = (ir + 1 + rng.integers(0, n - 1, 2)) % n
                try:
                    srv.mutate("a", EdgeDelta(insert_rows=ir, insert_cols=ic))
                except Exception as e:          # pragma: no cover
                    errors.append(e)

        def sampler():
            while not stop.is_set():
                try:
                    cs = srv.cache.stats()
                    if cs["hits"] + cs["misses"] != cs["lookups"]:
                        errors.append(AssertionError(
                            f"torn cache snapshot: {cs}"))
                    for t in graphs:
                        st = srv.stats(t)       # deep copy: never torn
                        if st["latency"]["lru_hit_rate"] > 1.0:
                            errors.append(AssertionError(str(st)))
                        slo = st["slo"]
                        # SLO conservation must hold in every mid-flight
                        # snapshot, not just at quiescence
                        if slo["admitted"] != slo["dispatched"] \
                                + slo["pending"] + slo["abandoned"]:
                            errors.append(AssertionError(
                                f"slo admission leak: {slo}"))
                        if slo["goodput"] + slo["deadline_misses"] \
                                + slo["no_deadline"] != slo["resolved"]:
                            errors.append(AssertionError(
                                f"slo resolve leak: {slo}"))
                        if slo["resolved"] > slo["dispatched"]:
                            errors.append(AssertionError(
                                f"resolved ahead of dispatch: {slo}"))
                except Exception as e:          # pragma: no cover
                    errors.append(e)
                time.sleep(0.001)

        threads = ([threading.Thread(target=submitter, args=(i,))
                    for i in range(4)]
                   + [threading.Thread(target=mutator),
                      threading.Thread(target=sampler)])
        for t in threads:
            t.start()
        for t in threads[:5]:                   # submitters + mutator
            t.join(timeout=120)
        for tks in tickets.values():            # every response arrives once
            for tk in tks:
                payload = tk.wait(timeout=60)
                assert payload is tk.result
                assert ("levels" in payload) or ("dist" in payload)
        stop.set()
        threads[-1].join(timeout=10)

    assert not errors, errors[:3]
    sched = srv.scheduler.stats()
    assert sched["pending"] == 0
    assert sched["admitted"] == sched["dispatched"]     # conservation
    assert sched["admitted"] == sum(len(v) for v in tickets.values())
    assert sched["depth_high_water"] <= sched["max_pending"]
    cs = srv.cache.stats()
    assert cs["hits"] + cs["misses"] == cs["lookups"]
    for t in graphs:                            # SLO ledger at quiescence
        slo = srv.stats(t)["slo"]
        assert slo["pending"] == 0
        assert slo["admitted"] == slo["dispatched"] + slo["abandoned"]
        assert slo["resolved"] == slo["dispatched"]
        assert slo["goodput"] + slo["deadline_misses"] \
            + slo["no_deadline"] == slo["resolved"]
        assert slo["slack_s"]["count"] == slo["goodput"] \
            + slo["deadline_misses"]

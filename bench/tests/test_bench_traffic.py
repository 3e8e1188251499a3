"""The query-stream generator reads every mix file and is deterministic
per seed; every seed offers the same queries at the same due times in
another order, and warm-up roots never reappear in the measured stream."""
import collections
import json

import numpy as np
import pytest

from bench import spec, traffic

MIXES = sorted(p.stem for p in (spec.BENCH_DIR / "traffic").glob("*.json"))
SECONDS = 51.0


def _degrees(n=4096, seed=0):
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, 5, n)
    deg[rng.random(n) < 0.3] = 0
    return deg


def _relabelled(seed, n=4096):
    """Degrees of the same structure under the seed's permutation."""
    perm = np.random.default_rng(seed).permutation(n)
    deg = np.empty(n, int)
    deg[perm] = _degrees(n)
    return deg, perm


def _mix(name):
    return json.loads((spec.BENCH_DIR / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_stream_deterministic_and_disjoint_from_warmup(mix):
    tf = _mix(mix)
    deg, perm = _relabelled(2 ** 31 + 3)
    warm, stream = traffic.make(tf, deg, perm, 2 ** 31 + 3, SECONDS)
    assert (warm, stream) == traffic.make(tf, deg, perm, 2 ** 31 + 3,
                                          SECONDS)
    assert sorted(warm) == sorted(tf["mix"])
    assert all(len(roots) == tf["batch"] for roots in warm.values())
    warm_roots = {r for roots in warm.values() for r in roots}
    assert not warm_roots & {r for _t, _a, r in stream}
    assert all(deg[r] > 0 for _t, _a, r in stream)


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    tf = _mix(mix)
    streams, work, gaps = [], [], []
    for seed in (1, 2 ** 31 + 99):
        deg, perm = _relabelled(seed)
        _w, stream = traffic.make(tf, deg, perm, seed, SECONDS)
        inv = np.argsort(perm)
        streams.append(stream)
        work.append(sorted((a, int(inv[r])) for _t, a, r in stream))
        due = np.array([t for t, _a, _r in stream])
        assert due[0] == 0.0 and np.all(np.diff(due) > 0)
        assert due[-1] < SECONDS
        assert len(stream) == round(tf["rate_per_s"] * SECONDS)
        gaps.append(np.round(np.sort(np.diff(due)), 9))
        counts = collections.Counter(a for _t, a, _r in stream)
        for alg, share in tf["mix"].items():
            assert abs(counts[alg] - share * len(stream)) <= 1
    assert streams[0] != streams[1]
    assert work[0] == work[1]
    # the same gaps in another order (each seed leaves out its last one)
    assert np.mean(np.isin(gaps[0], gaps[1])) > 0.95
    # the same algorithm at each position: only the roots move
    assert [a for _t, a, _r in streams[0]] == [a for _t, a, _r in streams[1]]


def test_arrival_gaps_are_exponential():
    rng = np.random.default_rng(0)
    due = traffic.arrivals("poisson", 5.0, 400.0, rng)
    gaps = np.diff(due)
    assert abs(gaps.mean() - 0.2) < 0.01
    assert abs(np.median(gaps) - 0.2 * np.log(2)) < 0.01


def test_even_arrivals_are_paced():
    rng = np.random.default_rng(0)
    due = traffic.arrivals("even", 4.8, 51.0, rng)
    assert due.size == round(4.8 * 51.0) and due[0] == 0.0
    np.testing.assert_allclose(np.diff(due), 51.0 / due.size)
    assert traffic.arrivals("even", 4.8, 51.0, np.random.default_rng(9)
                            ).tolist() == due.tolist()


@pytest.mark.parametrize("shares,length", [([0.5, 0.5], 9), ([0.25, 0.75], 40),
                                           ([1.0], 5)])
def test_interleave_keeps_every_prefix_near_its_share(shares, length):
    shares = np.asarray(shares)
    idx = traffic.interleave(shares, length)
    for i in range(1, length + 1):
        counts = np.bincount(idx[:i], minlength=shares.size)
        assert np.all(np.abs(counts - shares * i) < 1)


def test_zipf_concentrates_on_few_roots():
    deg, perm = _relabelled(1)
    base = {"batch": 8, "mix": {"bfs": 1.0}, "rate_per_s": 100.0,
            "arrivals": "poisson"}
    _w, uni = traffic.make({**base, "roots": "uniform"}, deg, perm, 1, 40.0)
    _w, zipf = traffic.make({**base, "roots": "zipf", "zipf_theta": 0.99},
                            deg, perm, 1, 40.0)
    top_uni = collections.Counter(r for _t, _a, r in uni).most_common(1)[0][1]
    top_zipf = collections.Counter(r for _t, _a, r in zipf).most_common(1)[0][1]
    assert top_zipf > 10 * top_uni
    assert len({r for *_x, r in zipf}) < 0.8 * len({r for *_x, r in uni})

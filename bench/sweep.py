"""Offered-load sweep of one cell, to find its knee: the highest rate the
served path sustains.

    python3 -m bench.sweep --workload kron-bfs-uniform --seed 11 \\
        --seconds 30 --rates 2,2.5,3,3.5

Runs the cell once per rate, in ascending order and in one process, with
the traffic file's ``rate_per_s`` replaced and one burst planted: the
queries due in a stretch of the window from its first fifth on, a
bucket's worth (``batch / rate_per_s`` seconds), are held back and sent
together at the stretch's end, as a stall of the host sends them. A rate
is sustained when every query is answered and the latency comes back
after the burst: the median latency of the queries due in the window's
last quarter stays under ``GROWTH`` times that of the queries due before
the burst. The flush takes every pending ticket, so a burst can leave the
server running more buckets per window, each window longer and so
gathering as many tickets again: above the knee the latency stays where
the burst put it (or grows), below it the windows shrink back. For each
rate the sweep prints one JSON line, and last the knee, the highest rate
of the sustained run of rates from the lowest, and four fifths of it, the
rate a cell's traffic file fixes. The benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

GROWTH = 1.25


def burst_window(traffic: dict, seconds: float) -> tuple[float, float]:
    """The stretch whose queries are held back: a bucket's worth of due
    times from the first fifth of the window on."""
    start = seconds / 5
    return start, start + float(traffic["batch"]) / float(traffic["rate_per_s"])


def with_burst(make):
    """``traffic.make`` with the queries due in :func:`burst_window` due at
    its end instead."""
    def make_burst(traffic, degrees, perm, seed, seconds):
        warm, stream = make(traffic, degrees, perm, seed, seconds)
        t0, t1 = burst_window(traffic, seconds)
        return warm, [(t1 if t0 <= t < t1 else t, alg, root)
                      for t, alg, root in stream]
    return make_burst


def recovery(records, seconds: float, burst_start: float) -> float:
    """Median latency of the queries due in the last quarter of the window
    over that of the queries due before the burst."""
    before = [r["t_done"] - r["t_due"] for r in records
              if r["ok"] and r["t_due"] < burst_start]
    last = [r["t_done"] - r["t_due"] for r in records
            if r["ok"] and r["t_due"] >= 0.75 * seconds]
    return float(np.median(last) / np.median(before))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)

    from bench import harness, spec, traffic

    loops = []

    class KeptLoop(harness.OpenLoop):
        def __init__(self, *a):
            super().__init__(*a)
            loops.append(self)

    harness.OpenLoop = KeptLoop
    traffic.make = with_burst(traffic.make)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    tf = spec.cell(args.workload)["traffic"]
    knee, sustained_so_far = None, True
    for rate in sorted(float(r) for r in args.rates.split(",")):
        try:
            r = harness.run_cell(args.workload, args.seed, args.seconds,
                                 False, time.perf_counter(),
                                 traffic_override={"rate_per_s": rate})
        except harness.NoAccelerator as e:
            print(f"bench.sweep: {e}", file=sys.stderr)
            return 1
        burst = burst_window({**tf, "rate_per_s": rate}, args.seconds)
        back = recovery(loops[-1].records, args.seconds, burst[0])
        sustained = r["failed"] == 0 and back < GROWTH
        sustained_so_far = sustained_so_far and sustained
        if sustained_so_far:
            knee = rate
        m = {k: v["value"] for k, v in r["metrics"].items()}
        print(json.dumps({"workload": args.workload, "offered": rate,
                          "burst": burst, "attempted": r["attempted"],
                          "answered_in_window": r["answered_in_window"],
                          "recovery": back, "sustained": sustained,
                          "correct": r["correct"], **m}), flush=True)
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "rate": None if knee is None else round(0.8 * knee, 2)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

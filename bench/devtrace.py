"""Profiler capture and its reduction to device busy time, the device
operations that took most time, and the idle gaps labelled by what the
host was doing.

The harness marks the measured window with a ``bench/window``
``TraceAnnotation`` and each client's calls with ``bench/submit`` and
``bench/wait``; everything here is read from the profiler's own trace
(``.xplane.pb``) with ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from collections import defaultdict

WINDOW = "bench/window"
CLIENT_WAIT = "bench/wait"
DEVICE_OPS_LINE = "XLA Ops"
TOP = 10


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(ops, w0, w1):
    """Each op's time clipped to [w0, w1) less the time of the ops nested
    inside it on the same line (a ``while`` holds its body's ops)."""
    out = defaultdict(float)
    stack = []   # [name, end, clipped length, children's clipped time]

    def close(frame):
        out[frame[0]] += frame[2] - frame[3]
        if stack:
            stack[-1][3] += frame[2]

    for name, s, e in sorted(ops, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        stack.append([name, e, max(0, min(e, w1) - max(s, w0)), 0])
    while stack:
        close(stack.pop())
    return out


_HLO = re.compile(r"^%?([\w.\-]+) = (.*?) ([\w\-]+)\(")


def short_name(hlo: str) -> str:
    """``fusion.8 fusion s32[131072,8]`` from XLA's long op text."""
    m = _HLO.match(hlo)
    if m is None:
        return hlo[:80]
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape[:60]}"


def load(trace_dir: str) -> dict:
    """Events of the newest trace under ``trace_dir`` as plain lists:
    ``{"host": [(name, start_ns, end_ns), ...],
       "devices": {plane: [(name, start_ns, end_ns), ...]}}``; device
    events are the ops of each device plane's ``XLA Ops`` line."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    host, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events]
        elif plane.name.startswith("/device:"):
            ops = [(e.name, e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == DEVICE_OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
    return {"host": host, "devices": devices}


def _overlap(a, b) -> int:
    """Total length of the intersection of two merged interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(events: dict, chips: int, pending=()) -> dict | None:
    """Reduce a trace to the window's device time.

    ``pending`` are the host-clock intervals (seconds from the window's
    start) in which each query was outstanding, sent but not answered.
    Returns ``window_s``; ``busy_s``, the union of device-op intervals
    averaged over the first ``chips`` device planes; ``pending_s`` and
    ``busy_pending_s``, the same restricted to the time with a query
    outstanding; and the ``breakdown`` lists: device ops by self time per
    chip, and the first chip's longest idle gaps, each labelled by the
    host event that overlaps it most (``no query outstanding`` where none
    was). None when the trace holds no window or no device ops."""
    windows = [(s, e) for name, s, e in events["host"] if name == WINDOW]
    planes = sorted(events["devices"])[:chips]
    if not windows or not planes:
        return None
    w0, w1 = windows[0]
    outstanding = _union([(max(w0, w0 + int(a * 1e9)),
                           min(w1, w0 + int(b * 1e9)))
                          for a, b in pending if b > a])
    busy, busy_pending, op_time = [], [], defaultdict(float)
    first_busy = None
    for plane in planes:
        ops = events["devices"][plane]
        for name, t in _self_times(ops, w0, w1).items():
            op_time[short_name(name)] += t / 1e9 / len(planes)
        merged = _union([(max(s, w0), min(e, w1)) for _n, s, e in ops
                         if min(e, w1) > max(s, w0)])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        busy_pending.append(_overlap(merged, outstanding) / 1e9)
        if first_busy is None:
            first_busy = merged
    gaps, cursor = [], w0
    for s, e in first_busy + [[w1, w1]]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    host = [(n, s, e) for n, s, e in events["host"]
            if n not in (WINDOW, CLIENT_WAIT) and e > s]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    labelled = [(_label(g, host, outstanding), (g[1] - g[0]) / 1e9)
                for g in longest]
    ops = sorted(op_time.items(), key=lambda x: -x[1])
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / len(planes),
        "pending_s": sum(e - s for s, e in outstanding) / 1e9,
        "busy_pending_s": sum(busy_pending) / len(planes),
        "breakdown": {"device_ops": [[n, t] for n, t in ops[:TOP]],
                      "idle_gaps": [[n, t] for n, t in labelled]},
    }


def _label(gap, host, outstanding) -> str:
    """What the host did in an idle gap: the host event that overlaps it
    most, ``no query outstanding`` when no query was waiting through
    most of it, else ``bench/wait``."""
    g0, g1 = gap
    if 2 * _overlap([list(gap)], outstanding) < g1 - g0:
        return "no query outstanding"
    best, best_overlap = CLIENT_WAIT, 0
    for name, s, e in host:
        overlap = min(e, g1) - max(s, g0)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def capture_dir(root: str, workload: str, seed: int) -> str:
    path = os.path.join(root, ".bench_trace", f"{workload}-{seed}")
    shutil.rmtree(path, ignore_errors=True)
    return path

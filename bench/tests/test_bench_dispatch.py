"""Graph families and query kinds are found by name, by the files under
bench/generators/ and bench/algorithms/.

The lookup must leave the cells it was made for reading as they read
before it: on one seed at a tiny scale, the same graph arrays, the same
checks (sound answers with planted errors, and the control) and the same
traversal roofline from the same buckets. The pinned values were read
with the code before the lookup by name (bench/reference.answers and
bench/roofline.VALUE_BYTES keyed by the kind's name)."""
import hashlib
import json
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from bench import graphgen, harness, spec, traffic

SEED = 2 ** 31 + 7
SCALE = 8

PINNED = {
    "kron-bfs-uniform": {
        "graph_sha256": "4c0f95ce74e2bd1f7efb45a340d84e48"
                        "fa5446a70601bdaaf4e3896d5934e040",
        "nnz": 4286,
        "checks": {"unanswered": 1, "bfs_wrong": 13, "bfs_checked": 48},
        "control_checks": {"unanswered": 1, "bfs_wrong": 1378,
                           "bfs_checked": 48},
        "frontier_sums": [10083, 11011, 9515, 9208, 8101, 9486, 9131, 8484,
                          9032, 8738, 10320, 8941, 9088, 8719, 10997, 7837,
                          8573, 8763, 9953, 7905],
        "traversal_roofline": 0.00017961904761904762},
    "urand-bfs-uniform": {
        "graph_sha256": "836dca11bfce3a1790390cf123fb258d"
                        "0786bc93dc5c801e50c062bb1e43b57f",
        "nnz": 7666,
        "checks": {"unanswered": 1, "bfs_wrong": 13, "bfs_checked": 48},
        "control_checks": {"unanswered": 1, "bfs_wrong": 733,
                           "bfs_checked": 48},
        "frontier_sums": [14178, 13481, 14218, 14243, 14072, 13801, 13929,
                          13140, 14125, 13835, 13466, 14281, 13205, 14027,
                          14087, 13912, 13910, 13832, 13810, 13394],
        "traversal_roofline": 0.0002705211233211233},
    "kron-mixed-zipf": {
        "graph_sha256": "4c0f95ce74e2bd1f7efb45a340d84e48"
                        "fa5446a70601bdaaf4e3896d5934e040",
        "nnz": 4286,
        "checks": {"unanswered": 1, "bfs_wrong": 12, "bfs_checked": 48,
                   "sssp_wrong": 11, "sssp_checked": 48},
        "control_checks": {"unanswered": 1, "bfs_wrong": 1305,
                           "bfs_checked": 48, "sssp_wrong": 1471,
                           "sssp_checked": 48},
        "frontier_sums": [10927, 8104, 7255, 10393, 9425, 10832, 10306,
                          11197, 8831, 8941, 8431, 8759, 9923, 8254, 9460,
                          7926, 11499, 7624, 9816, 7356],
        "traversal_roofline": 0.00026794334554334554},
}


def _readings(workload: str) -> dict:
    """One tiny cell's graph, checks and roofline, with the served answers
    stood in for by the reference's, every fifth altered at its root and
    one query left unanswered."""
    c = spec.cell(workload)
    rows, cols, n, perm = graphgen.generate({**c["config"], "scale": SCALE},
                                            SEED)
    digest = hashlib.sha256(b"".join(np.ascontiguousarray(a).tobytes()
                                     for a in (rows, cols, perm))).hexdigest()
    degrees = np.bincount(rows, minlength=n)
    tf = {**c["traffic"], "rate_per_s": 40.0}
    _warm, stream = traffic.make(tf, degrees, perm, SEED, 4.0)
    hops_of = spec.algorithm("bfs")
    records = []
    for i, (due, name, root) in enumerate(stream):
        alg = spec.algorithm(name)
        rec = {"i": i, "alg": name, "root": root, "ok": i != 11,
               "t_due": due, "t_done": due + 0.5}
        if rec["ok"]:
            got = np.array(alg.answers(rows, cols, n, [root], SEED)[0])
            if i % 5 == 0:
                got[root] = 3
            hops = hops_of.answers(rows, cols, n, [root], SEED)[0]
            rec["payload"] = {alg.PAYLOAD_FIELD: got,
                              "iterations": int(hops.max()) + 1}
        records.append(rec)
    buckets = []
    for name in sorted(tf["mix"]):
        mine = [r for r in records if r["ok"] and r["alg"] == name]
        for lo in range(0, len(mine), tf["batch"]):
            chunk = mine[lo:lo + tf["batch"]]
            buckets.append({
                "alg": name, "roots": [r["root"] for r in chunk],
                "trips": max(r["payload"]["iterations"] for r in chunk),
                "records": chunk})
    record = {"n": n, "nnz": int(rows.size), "busy_s": 0.5, "chips": 1,
              "peaks": spec.peaks("TPU v5 lite"),
              "buckets": harness._bucket_traffic(buckets, rows, cols, n,
                                                 degrees)}

    def values(checks):
        return {k: v["value"] for k, v in checks.items()}

    return {
        "graph_sha256": digest, "nnz": int(rows.size),
        "checks": values(harness.compare(records, rows, cols, n, SEED)),
        "control_checks": values(harness.compare(records, rows, cols, n,
                                                 SEED, control=True)),
        "frontier_sums": [sum(b["frontier_entries"])
                          for b in record["buckets"]],
        "traversal_roofline": spec.metric_reader("traversal_roofline")(
            record)}


@pytest.mark.parametrize("workload", sorted(PINNED))
def test_cells_read_as_before_the_lookup(workload):
    got = _readings(workload)
    want = PINNED[workload]
    assert {k: v for k, v in got.items() if k != "traversal_roofline"} == {
        k: v for k, v in want.items() if k != "traversal_roofline"}
    assert got["traversal_roofline"] == pytest.approx(
        want["traversal_roofline"], rel=1e-12)


def test_recorded_roofline_reads_as_before_the_lookup():
    record = json.loads((Path(__file__).parent / "data" / "record.json")
                        .read_text())
    assert spec.metric_reader("traversal_roofline")(record) == \
        pytest.approx(0.006254193980084834, rel=1e-12)


def test_every_named_generator_and_algorithm_has_its_module():
    bm = spec.benchmark()
    for cfg in bm["configs"]:
        family = json.loads((spec.ROOT / cfg["file"]).read_text())["generator"]
        assert callable(spec.generator(family).tuples)
    for w in bm["workloads"]:
        for name in spec.cell(w["name"])["traffic"]["mix"]:
            alg = spec.algorithm(name)
            for fn in ("answers", "count_wrong", "control",
                       "frontier_entries", "least_bytes"):
                assert callable(getattr(alg, fn)), (name, fn)
            assert isinstance(alg.PAYLOAD_FIELD, str)
    with pytest.raises(KeyError, match="known"):
        spec.algorithm("no-such-kind")


RING = '''
"""A test family: one cycle through every vertex."""
import numpy as np


def tuples(cfg, rng):
    n = 1 << int(cfg["scale"])
    src = rng.permutation(n)
    return src, np.roll(src, 1), n
'''

HOPS2 = '''
"""A test kind: every vertex within two hops of the root."""
import numpy as np

from bench import reference, roofline

PAYLOAD_FIELD = "near"


def answers(rows, cols, n, roots, seed):
    hops = reference.hop_counts(rows, cols, n, roots)
    return ((hops >= 0) & (hops <= 2)).astype(np.int64)


def count_wrong(got, want):
    return int(np.sum(np.asarray(got) != want))


def control(rows, cols, n, roots, seed, want):
    return np.zeros_like(want)


def frontier_entries(payloads, rows, cols, n, roots, degrees, trips):
    hops = reference.hop_counts(rows, cols, n, roots)
    return roofline.frontier_entries(hops, degrees, trips)


def least_bytes(n, nnz, k, frontier):
    return roofline.least_bytes(n, nnz, k, frontier, 0)
'''

DRIVE = '''
import json
import numpy as np
import bench
from bench import graphgen, harness, spec

cfg = {"generator": "ring", "scale": 5, "structure_seed": 1}
rows, cols, n, perm = graphgen.generate(cfg, 3)
alg = spec.algorithm("hops2")
roots = [int(perm[0]), int(perm[7])]
want = alg.answers(rows, cols, n, roots, 3)
records = [{"alg": "hops2", "root": r, "ok": True,
            "payload": {"near": w, "iterations": 3}}
           for r, w in zip(roots, want)]
degrees = np.bincount(rows, minlength=n)
buckets = [{"alg": "hops2", "roots": roots, "trips": 3, "records": records}]
record = {"n": n, "nnz": int(rows.size), "busy_s": 1.0, "chips": 1,
          "peaks": {"hbm_bytes_per_s": 1.0},
          "buckets": harness._bucket_traffic(buckets, rows, cols, n,
                                             degrees)}
print(json.dumps({
    "bench": bench.__file__, "n": n, "nnz": int(rows.size),
    "near": [int(w.sum()) for w in want],
    "checks": harness.compare(records, rows, cols, n, 3),
    "control": harness.compare(records, rows, cols, n, 3, control=True),
    "frontier": record["buckets"][0]["frontier_entries"],
    "roofline": spec.metric_reader("traversal_roofline")(record)}))
'''


def test_added_files_bring_a_new_family_and_kind(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench" / "generators" / "ring.py").write_text(RING)
    (tmp_path / "bench" / "algorithms" / "hops2.py").write_text(HOPS2)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(DRIVE)],
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=120, env={"PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stderr
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert Path(out["bench"]).parent == tmp_path / "bench"
    # a cycle of 32 vertices, both directions stored
    assert (out["n"], out["nnz"]) == (32, 64)
    assert out["near"] == [5, 5]
    assert out["checks"]["hops2_wrong"]["value"] == 0
    assert out["checks"]["hops2_checked"]["value"] == 2
    assert out["control"]["hops2_wrong"]["value"] == 10
    # hop 0, 1 and 2 frontiers of two roots 7 apart: 2, 4 and 4 vertices
    assert out["frontier"] == [4, 8, 8]
    assert out["roofline"] == pytest.approx(100.0 * 4 * 20)

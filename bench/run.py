"""Benchmark entry point: one run of one cell on the chips of this machine.

    python3 -m bench.run --workload kron-bfs-uniform --seed 7 \\
        --seconds 45 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled run. The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and ``checks`` last: each
number compared with the reference beside its limit). The same checks are
the last lines of standard error.

The run exits nonzero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for. It keeps JAX's compile cache in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache`` at
the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: compare the control (each algorithm's "
                         "control answers, bench/algorithms/<alg>.py) in "
                         "the program's place; it must come out not "
                         "correct")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from bench import harness, spec

    src = spec.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench.run: no program sources under {src}", file=sys.stderr)
        return 2
    # the TPU runtime would otherwise log to a directory outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START,
                                  control=bool(args.control))
    except harness.NoAccelerator as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 1
    print(f"programs traced in the window: "
          f"{result['programs_traced_in_window']}; the sender ran at most "
          f"{result['sender_late_s_max']:.6f} s late; loop trips per "
          f"bucket: {result['bucket_trips']}", file=sys.stderr)
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The least HBM traffic of a served traversal bucket, counted from the
algorithm and not from the implementation.

One iteration of a bucket of ``k`` queries over a graph of ``n`` vertices
and ``nnz`` stored entries can be done either way round:

* pull (SpMV): read every entry's column index and value, the row
  pointers, and read and write the ``[k, n]`` state at 4 bytes:
  ``nnz * (4 + value_bytes) + 4 * (n + 1) + 2 * k * n * 4``;
* push (SpMSpV): read at least the index and value of every entry in the
  columns of the union frontier: ``frontier_entries * (4 + value_bytes)``.

The least traffic of the iteration is the smaller of the two, so the
bound holds whichever kernel a later change picks. ``value_bytes`` is 0
for BFS (its matrix is a pattern) and 4 for SSSP (float32 weights); a
change that narrows these dtypes needs a benchmark change to recount.
"""
from __future__ import annotations

import numpy as np

VALUE_BYTES = {"bfs": 0, "sssp": 4}


def frontier_entries(hops: np.ndarray, degrees: np.ndarray,
                     trips: int) -> list:
    """Stored entries in the columns of each iteration's union frontier.

    ``hops`` is the bucket's ``[k, n]`` hop counts (-1 unreachable). The
    frontier read at iteration ``t`` holds every vertex first reached at
    hop ``t``; for SSSP those are a subset of the vertices whose distance
    changed, so the count stays a lower bound."""
    out = []
    for t in range(trips):
        live = np.any(hops == t, axis=0)
        out.append(int(degrees[live].sum()))
    return out


def least_bytes(alg: str, n: int, nnz: int, k: int,
                frontier: list) -> float:
    """Least HBM bytes of one bucket: per iteration the cheaper of pull
    and push (see the module docstring)."""
    vb = VALUE_BYTES[alg]
    pull = nnz * (4 + vb) + 4 * (n + 1) + 2 * k * n * 4
    return float(sum(min(pull, e * (4 + vb)) for e in frontier))

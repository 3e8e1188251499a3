"""The least HBM traffic of a served traversal bucket, counted from the
algorithm and not from the implementation.

One iteration of a bucket of ``k`` queries over a graph of ``n`` vertices
and ``nnz`` stored entries can be done either way round:

* pull (SpMV): read every entry's column index and value, the row
  pointers, and read and write the ``[k, n]`` state at 4 bytes:
  ``nnz * (4 + value_bytes) + 4 * (n + 1) + 2 * k * n * 4``;
* push (SpMSpV): read at least the index and value of every entry in the
  columns of the vertices the iteration reads from:
  ``frontier_entries * (4 + value_bytes)``.

The least traffic of the iteration is the smaller of the two, so the
bound holds whichever kernel a later change picks. Each query kind's
module (``bench/algorithms/<alg>.py``, found by name) says which vertices
an iteration reads from and what ``value_bytes`` its matrix stores (0 for
a pattern, 4 for float32 values); a change that narrows these dtypes
needs a benchmark change to recount.
"""
from __future__ import annotations

import numpy as np


def frontier_entries(hops: np.ndarray, degrees: np.ndarray,
                     trips: int) -> list:
    """Stored entries in the columns of each iteration's union frontier.

    ``hops`` is the bucket's ``[k, n]`` hop counts (-1 unreachable). The
    frontier read at iteration ``t`` holds every vertex first reached at
    hop ``t``; for a kind whose frontier is a superset of those (SSSP's
    changed distances), the count stays a lower bound."""
    out = []
    for t in range(trips):
        live = np.any(hops == t, axis=0)
        out.append(int(degrees[live].sum()))
    return out


def least_bytes(n: int, nnz: int, k: int, frontier: list,
                value_bytes: int) -> float:
    """Least HBM bytes of one bucket: per iteration the cheaper of pull
    and push (see the module docstring)."""
    pull = nnz * (4 + value_bytes) + 4 * (n + 1) + 2 * k * n * 4
    return float(sum(min(pull, e * (4 + value_bytes)) for e in frontier))

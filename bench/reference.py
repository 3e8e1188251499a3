"""What the benchmark's plain references share, and the comparison's
frame.

Each query kind has its own reference, ``bench/algorithms/<alg>.py``,
found by the kind's name (see ``bench/spec.algorithm``). Like this module
it imports nothing of the program, and works over the directed entries
``rows[i] -> cols[i]`` of the generated graph. Here are the pieces they
share: the scipy adjacency, every vertex's hop count from each root, and
the early-stopping control of the exact kinds.

The comparison that decides ``correct`` (``bench/harness.compare``) asks
every query sent to be answered, and a sample of the answers of each kind
to pass that kind's ``count_wrong`` with the limit 0; the control's
answers, put in the program's place, must fail it.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import shortest_path


def adjacency(rows, cols, n, data) -> sp.csr_matrix:
    return sp.csr_matrix((data, (rows, cols)), shape=(n, n))


def hop_counts(rows, cols, n, roots) -> np.ndarray:
    """[len(roots), n] hop counts along rows -> cols; -1 where unreachable."""
    adj = adjacency(rows, cols, n, np.ones(rows.shape[0]))
    d = shortest_path(adj, method="D", directed=True, unweighted=True,
                      indices=np.asarray(roots))
    return np.where(np.isinf(d), -1, d).astype(np.int64)


def truncate_last_level(ref_rows: np.ndarray, hops: np.ndarray,
                        unreached) -> np.ndarray:
    """A control of the exact kinds: each answer with the vertices of its
    deepest hop level set to ``unreached``, as a traversal stopped one
    iteration early leaves them. ``hops`` are the hop counts from the same
    roots."""
    out = np.array(ref_rows, copy=True)
    deepest = hops.max(axis=1, keepdims=True)
    last = (hops == deepest) & (deepest > 0)
    out[last] = unreached
    return out

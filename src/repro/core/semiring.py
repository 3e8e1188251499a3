"""Algebraic semirings for linear-algebraic graph processing (paper §2.1, Table 1).

A semiring generalizes (+, x) to (add ⊕, mul ⊗) with identities (zero, one).
The same SpMV/SpMSpV engine then runs BFS (⟨∨,∧⟩), SSSP (⟨min,+⟩) and
PPR (⟨+,×⟩) just by swapping the semiring — the paper's Table 1. The
analytics subsystem (graphs/analytics.py) extends the table with
⟨min,×⟩ (connected components) and ⟨+,∧⟩ (triangle counting).

Semirings here are *static* (python-level) objects: kernels stage the chosen
ops at trace time, so there is no runtime dispatch cost.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Semiring:
    """⟨S, ⊕, ⊗, zero, one⟩ with JAX-traceable ops.

    add/mul are elementwise binary ops; add_reduce reduces an axis with ⊕.
    ``zero`` is the ⊕-identity (and ⊗-annihilator), ``one`` the ⊗-identity.
    ``collective`` names the lax collective that implements a distributed
    ⊕-reduction (used by core.distributed for the Merge phase).
    """

    name: str
    add: Callable[[Array, Array], Array]
    mul: Callable[[Array, Array], Array]
    zero: Any
    one: Any
    dtype: Any
    collective: str  # one of: "psum", "pmin", "pmax", "por"

    @property
    def mxu_eligible(self) -> bool:
        """True iff ⟨⊕,⊗⟩ is ordinary ⟨+,×⟩, so a kernel may lower the
        reduction to jnp.dot on the MXU. ``collective == "psum"`` is NOT
        sufficient: ⟨+,∧⟩ (triangle counting) ⊕-reduces with psum but its
        ⊗ is min, which dot would silently get wrong."""
        return self.add is jnp.add and self.mul is jnp.multiply

    @property
    def exact_in_any_order(self) -> bool:
        """True iff ⊕-reducing in another association gives the same bits:
        min, max and OR always, a sum only over integers."""
        return (self.collective != "psum"
                or jnp.issubdtype(self.dtype, jnp.integer))

    def add_reduce(self, x: Array, axis: int | tuple[int, ...],
                   keepdims: bool = False) -> Array:
        kw = {"axis": axis, "keepdims": keepdims}
        if self.collective == "psum":
            return jnp.sum(x, **kw)
        if self.collective == "pmin":
            return jnp.min(x, **kw)
        if self.collective == "pmax":
            return jnp.max(x, **kw)
        if self.collective == "por":
            return jnp.any(x, **kw) if x.dtype == jnp.bool_ else jnp.max(x, **kw)
        raise ValueError(self.collective)

    def segment_reduce(self, data: Array, segment_ids: Array, num_segments: int) -> Array:
        """⊕-reduce ``data`` into ``num_segments`` buckets (CSR/COO kernels).

        Every ⊕-scatter of the served matvecs runs here, under the
        ``segment_reduce`` name scope, so the device trace attributes its
        ops to one stable name; another kernel doing this reduction keeps
        the scope."""
        with jax.named_scope("segment_reduce"):
            if self.collective == "psum":
                return jax.ops.segment_sum(data, segment_ids, num_segments)
            if self.collective in ("pmin",):
                # empty segments come back +inf == min_plus zero, already correct
                return jax.ops.segment_min(data, segment_ids, num_segments)
            if self.collective in ("pmax", "por"):
                # empty segments come back dtype-min; clamp to the ⊕-identity
                out = jax.ops.segment_max(data, segment_ids, num_segments)
                return jnp.maximum(out, jnp.asarray(self.zero, out.dtype))
            raise ValueError(self.collective)

    def segment_reduce_sorted(self, data: Array, segment_ids: Array,
                              row_ptr: Array, steps: int) -> Array:
        """⊕-reduce the row-sorted entries of a CSR matrix: ``data`` is
        ``[B, nnz]`` (entries along the minor axis), ``segment_ids``
        ``[nnz]`` non-decreasing, ``row_ptr`` ``[m+1]``; returns ``[B, m]``.

        A segmented Hillis–Steele scan: step k ⊕-folds each entry's
        neighbour 2^k places to its left when both lie in one row, so after
        ``steps`` steps an entry holds the ⊕ of its row's last 2^steps
        entries up to itself; ``steps`` ≥ log2 of the longest row makes
        each row's last entry its total, read through ``row_ptr`` (empty
        rows get ``zero``). No scatter and no sort: exact for min, max, OR
        and integer sums; a float sum is only associated differently
        (``exact_in_any_order``). Runs under the
        ``segment_reduce`` scope, the steps under ``row_scan`` and the read
        under ``row_ends``."""
        with jax.named_scope("segment_reduce"):
            zero = jnp.asarray(self.zero, data.dtype)
            # Entries along the lanes. A column gather ``xs[:, cols]``
            # comes out of the TPU compiler as rows of B values (B minor,
            # padded to 128 lanes); left alone, that layout would run
            # through every step at 16x the bytes for B = 8. The
            # constraint makes the compiler transpose once, here.
            v = with_layout_constraint(data, Layout(major_to_minor=(0, 1)))
            with jax.named_scope("row_scan"):
                for k in range(steps):
                    s = 1 << k
                    if s >= v.shape[-1]:
                        break
                    # shift right by s along the entries: one pad with a
                    # negative high edge, which XLA fuses into the step
                    left = jax.lax.pad(v, zero, [(0, 0, 0), (s, -s, 0)])
                    same = jax.lax.pad(segment_ids, jnp.int32(-1),
                                       [(s, -s, 0)]) == segment_ids
                    v = jnp.where(same[None], self.add(v, left), v)
            with jax.named_scope("row_ends"):
                end = row_ptr[1:]
                y = jnp.take(v, jnp.maximum(end - 1, 0), axis=1, mode="clip")
                return jnp.where((end > row_ptr[:-1])[None], y, zero)

    def preduce(self, x: Array, axis_name: str) -> Array:
        """Distributed ⊕-reduction over a mesh axis (the paper's Merge phase,
        executed on-fabric instead of on the host CPU)."""
        if self.collective == "psum":
            return jax.lax.psum(x, axis_name)
        if self.collective == "pmin":
            return jax.lax.pmin(x, axis_name)
        if self.collective in ("pmax", "por"):
            return jax.lax.pmax(x, axis_name)
        raise ValueError(self.collective)

    def matvec(self, a_dense: Array, x: Array) -> Array:
        """Dense reference y_i = ⊕_j a_ij ⊗ x_j (oracle for tests)."""
        return self.add_reduce(self.mul(a_dense, x[None, :]), axis=1)


def _saturating_or(a: Array, b: Array) -> Array:
    return jnp.maximum(a, b)


# BFS: boolean ⟨∨,∧⟩ over {0,1}; stored as int32 0/1 (TPU-friendly; bool VREGs
# are int lanes anyway). zero=0, one=1.
BOOL_OR_AND = Semiring(
    name="bool_or_and",
    add=_saturating_or,
    mul=jnp.minimum,  # AND on {0,1}
    zero=0,
    one=1,
    dtype=jnp.int32,
    collective="por",
)

# SSSP: tropical ⟨min,+⟩ over ℝ∪{∞}. zero=+inf, one=0.
MIN_PLUS = Semiring(
    name="min_plus",
    add=jnp.minimum,
    mul=jnp.add,
    zero=jnp.inf,
    one=0.0,
    dtype=jnp.float32,
    collective="pmin",
)

# PPR / PageRank: standard arithmetic ⟨+,×⟩.
PLUS_TIMES = Semiring(
    name="plus_times",
    add=jnp.add,
    mul=jnp.multiply,
    zero=0.0,
    one=1.0,
    dtype=jnp.float32,
    collective="psum",
)

# Connected components: ⟨min,×⟩ over ℝ₊∪{∞} — min-label propagation.
# With unit edge weights, y_i = min_j (1 × l_j) is "smallest neighbour
# label"; iterating l ← l ⊕ y floods component minima (graphs/analytics.py).
# Domain constraint: operands must stay strictly positive (inf × 0 = nan
# would poison the min-reduction), which vertex labels 1..n satisfy.
MIN_TIMES = Semiring(
    name="min_times",
    add=jnp.minimum,
    mul=jnp.multiply,
    zero=jnp.inf,
    one=1.0,
    dtype=jnp.float32,
    collective="pmin",
)

# Triangle counting: ⟨+,∧⟩ over {0,1}⊂ℤ — C = (L ⊕.⊗ Lᵀ) ⊙ L counts, per
# masked edge, the common in-neighbours of its endpoints (paper §5.1's
# matrix-matrix workload class). ∧ on {0,1} is min; ⊕-reduce is a plain sum
# so the count comes out in ℤ.
PLUS_AND = Semiring(
    name="plus_and",
    add=jnp.add,
    mul=jnp.minimum,
    zero=0,
    one=1,
    dtype=jnp.int32,
    collective="psum",
)

SEMIRINGS: dict[str, Semiring] = {
    s.name: s for s in (BOOL_OR_AND, MIN_PLUS, PLUS_TIMES, MIN_TIMES, PLUS_AND)
}


def get(name: str) -> Semiring:
    return SEMIRINGS[name]

"""The benchmark's generators: deterministic per seed, isomorphic across
seeds, and holding the Graph500 and GAP parameters."""
import numpy as np
import pytest

from bench import graphgen, spec

KRON = {"generator": "kronecker", "scale": 10, "edgefactor": 16,
        "structure_seed": 3,
        "initiator": {"A": 0.57, "B": 0.19, "C": 0.19, "D": 0.05}}
URAND = {"generator": "urand", "scale": 10, "edgefactor": 16,
         "structure_seed": 4}


@pytest.mark.parametrize("cfg", [KRON, URAND], ids=["kron", "urand"])
def test_same_seed_same_graph(cfg):
    a, b = graphgen.generate(cfg, 77), graphgen.generate(cfg, 77)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("cfg", [KRON, URAND], ids=["kron", "urand"])
def test_seeds_give_isomorphic_graphs(cfg):
    r1, c1, n, p1 = graphgen.generate(cfg, 1)
    r2, c2, _, p2 = graphgen.generate(cfg, 2 ** 31 + 5)
    assert r1.shape == r2.shape
    assert not np.array_equal(r1, r2)
    # undoing each permutation gives back one and the same edge set
    inv1, inv2 = np.argsort(p1), np.argsort(p2)
    e1 = np.sort(inv1[r1].astype(np.int64) * n + inv1[c1])
    e2 = np.sort(inv2[r2].astype(np.int64) * n + inv2[c2])
    np.testing.assert_array_equal(e1, e2)


@pytest.mark.parametrize("cfg", [KRON, URAND], ids=["kron", "urand"])
def test_undirected_simple(cfg):
    rows, cols, n, _perm = graphgen.generate(cfg, 9)
    assert n == 2 ** cfg["scale"]
    assert rows.dtype == np.int32 and cols.dtype == np.int32
    assert np.all(rows != cols)
    keys = rows.astype(np.int64) * n + cols
    assert np.unique(keys).size == keys.size
    back = np.sort(cols.astype(np.int64) * n + rows)
    np.testing.assert_array_equal(np.sort(keys), back)
    assert rows.size <= 2 * cfg["edgefactor"] * n


def test_kronecker_initiator_bits():
    """Each bit level puts a tuple in the top half of the rows with
    probability C + D and, within a half, in the right half of the columns
    with probability B / (A + B) (top) or D / (C + D) (bottom)."""
    rng = np.random.default_rng(0)
    a, b, c = 0.57, 0.19, 0.19
    src, dst = spec.generator("kronecker").bit_levels(12, 16, a, b, c, rng)
    assert src.size == 16 * 2 ** 12
    ii = (src & 1).astype(bool)
    jj = (dst & 1).astype(bool)
    assert abs(ii.mean() - (1 - a - b)) < 0.01
    assert abs(jj[~ii].mean() - b / (a + b)) < 0.01
    assert abs(jj[ii].mean() - 0.05 / 0.24) < 0.02


def test_kron_skewed_urand_flat():
    kr, _, n, _ = graphgen.generate(KRON, 5)
    ur, _, _, _ = graphgen.generate(URAND, 5)
    kdeg, udeg = np.bincount(kr, minlength=n), np.bincount(ur, minlength=n)
    assert kdeg.max() > 10 * kdeg.mean()
    assert udeg.max() < 3 * udeg.mean()
    assert np.count_nonzero(udeg) == n

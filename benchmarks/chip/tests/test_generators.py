"""The benchmark's graph generators at tiny sizes."""
import numpy as np
import pytest

import loader


def _gen(bench_root, name):
    return loader.module(bench_root, "graphs", name)


def _simple_symmetric(n, src, dst):
    key = src * n + dst
    assert np.all(np.diff(key) > 0), "sorted, no duplicates"
    assert not np.any(src == dst), "no self-loops"
    assert np.array_equal(np.sort(dst * n + src), key), "both directions"
    assert src.min() >= 0 and max(src.max(), dst.max()) < n


KRON = {"scale": 10, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}


def test_kronecker_shape_and_seed(bench_root):
    kron = _gen(bench_root, "kronecker")
    n, src, dst = kron.generate(KRON, 2**31 + 11)
    assert n == 1024
    _simple_symmetric(n, src, dst)
    # at most both directions of the 16 * n generated edges survive
    assert 0.5 * 16 * n < len(src) <= 2 * 16 * n
    deg = np.bincount(src, minlength=n)
    assert deg.max() > 20 * deg.mean(), "Kronecker degrees are skewed"
    again = kron.generate(KRON, 2**31 + 11)
    assert all(np.array_equal(a, b) for a, b in zip(again[1:], (src, dst)))
    other = kron.generate(KRON, 5)
    assert not (len(other[1]) == len(src) and np.array_equal(other[1], src))


def test_kronecker_quadrant_probabilities(bench_root):
    """Each level puts an edge in quadrant (i bit, j bit) with the
    initiator's probabilities A, B, C and D = 1 - A - B - C."""
    kron = _gen(bench_root, "kronecker")
    i, j = kron.edges(1, 400_000, 0.57, 0.19, 0.19,
                      np.random.default_rng(2**31 + 1))
    for (bi, bj), p in {(0, 0): 0.57, (0, 1): 0.19, (1, 0): 0.19,
                        (1, 1): 0.05}.items():
        assert abs(np.mean((i == bi) & (j == bj)) - p) < 0.005
    i, j = kron.edges(12, 400_000, 0.57, 0.19, 0.19,
                      np.random.default_rng(0))
    assert i.max() < 4096 and j.max() < 4096
    # vertex 0 (all bits 0) is the hub before relabelling
    assert np.bincount(i).argmax() == 0


@pytest.mark.parametrize("side", [2, 7, 16])
def test_lattice_is_a_relabelled_grid(bench_root, side):
    lat = _gen(bench_root, "lattice")
    n, src, dst = lat.generate({"side": side}, 3)
    assert n == side * side
    _simple_symmetric(n, src, dst)
    assert len(src) == 4 * side * (side - 1)
    deg = np.bincount(src, minlength=n)
    want = np.zeros(n, np.int64)
    grid = np.zeros((side, side), np.int64)
    grid[:, :-1] += 1
    grid[:, 1:] += 1
    grid[:-1, :] += 1
    grid[1:, :] += 1
    want[:] = np.sort(grid.ravel())
    assert np.array_equal(np.sort(deg), want)
    n2, src2, _ = lat.generate({"side": side}, 4)
    if side > 2:
        assert not np.array_equal(src2, src), "the seed relabels the ids"

"""Four-neighbour square lattice, the shape of the repository's
``grid_road`` (maximum degree 4, diameter ``2 * (side - 1)``), with its
vertex ids relabelled by a random permutation drawn from ``seed``.  Every
seed gives the same graph up to the order of its ids."""
from __future__ import annotations

import numpy as np


def generate(cfg: dict, seed: int):
    """Returns ``(n, src, dst)``: int64 arrays, both directions of every
    lattice edge, sorted by (src, dst)."""
    side = int(cfg["side"])
    n = side * side
    idx = np.arange(n, dtype=np.int64).reshape(side, side)
    a = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    b = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    perm = np.random.default_rng(seed).permutation(n)
    s = perm[np.concatenate([a, b])]
    d = perm[np.concatenate([b, a])]
    key = np.sort(s * n + d)
    return n, key // n, key % n

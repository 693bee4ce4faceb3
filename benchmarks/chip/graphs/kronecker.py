"""Graph500 Kronecker generator (Graph500 specification, "Graph
Generation"), stored the way LDBC Graphalytics' graph500-* sets are:
undirected, both directions kept, self-loops and duplicates dropped.

Each of the ``edgefactor * 2**scale`` edges picks one quadrant of the
adjacency matrix per level, with probabilities A, B, C and D = 1-A-B-C,
as the specification's reference code does.  The vertex ids are then
relabelled by a random permutation.  Everything is drawn from ``seed``.
"""
from __future__ import annotations

import numpy as np


def generate(cfg: dict, seed: int):
    """Returns ``(n, src, dst)``: int64 arrays, symmetric, deduplicated,
    sorted by (src, dst), no self-loops."""
    scale, ef = int(cfg["scale"]), int(cfg["edgefactor"])
    a, b, c = float(cfg["A"]), float(cfg["B"]), float(cfg["C"])
    rng = np.random.default_rng(seed)
    i, j = edges(scale, ef << scale, a, b, c, rng)
    perm = rng.permutation(1 << scale)
    return symmetric_simple(1 << scale, perm[i], perm[j])


def edges(scale: int, m: int, a: float, b: float, c: float, rng):
    """``m`` directed edges ``(i, j)`` before relabelling: at each of the
    ``scale`` levels one bit of ``i`` and of ``j``, as the specification's
    reference code draws them."""
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for level in range(scale):
        ii = rng.random(m, dtype=np.float32) > ab
        jj = rng.random(m, dtype=np.float32) > np.where(ii, c_norm, a_norm)
        i |= ii.astype(np.int64) << level
        j |= jj.astype(np.int64) << level
    return i, j


def symmetric_simple(n: int, i: np.ndarray, j: np.ndarray):
    """Both directions of every edge, without self-loops or duplicates."""
    s = np.concatenate([i, j])
    d = np.concatenate([j, i])
    keep = s != d
    key = np.unique(s[keep] * n + d[keep])
    return n, key // n, key % n

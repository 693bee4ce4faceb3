"""PageRank reference: a NumPy power iteration in float64 with the
engine's dangling rule (rank held by vertices without out-edges is
dropped, not redistributed).  Imports nothing of the program."""
from __future__ import annotations

import numpy as np


def reference(n, src, dst, params, dtype=np.float64):
    """(n,) ranks after ``params["n_iters"]`` iterations.  ``dtype`` is
    the precision every value is rounded to after each operation; sums
    accumulate in float64 before that rounding."""
    iters, damping = int(params["n_iters"]), float(params["damping"])
    deg = np.bincount(src, minlength=n)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1), 0.0).astype(dtype)
    pr = np.full(n, 1.0 / n).astype(dtype)
    base = np.asarray((1 - damping) / n).astype(dtype)
    for _ in range(iters):
        contrib = (pr * inv).astype(dtype)
        inbox = np.bincount(dst, weights=contrib[src].astype(np.float64),
                            minlength=n).astype(dtype)
        pr = (base + (damping * inbox).astype(dtype)).astype(dtype)
    return pr.astype(np.float64)


def control(n, src, dst, params):
    """The reference one precision step below the configuration's
    float32: every value rounded to bfloat16."""
    import ml_dtypes
    return reference(n, src, dst, params, dtype=ml_dtypes.bfloat16)


def compare(got, ref):
    """Numbers compared: the widest relative gap of any vertex's rank.
    Every rank is at least (1 - damping) / n > 0."""
    got = np.asarray(got, np.float64)
    return {"pr_max_rel_err": float(np.max(np.abs(got - ref) / ref))}


def from_program(state, perm):
    """The program's (M, n_loc) ranks in original vertex order."""
    return np.asarray(state).reshape(-1)[perm]

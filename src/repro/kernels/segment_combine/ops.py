"""jit'd wrapper + host-side edge packing for the segment_combine kernel."""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.kernels.segment_combine.kernel import segment_combine_blocks
from repro.kernels.segment_combine.ref import segment_combine_blocks_ref


def _identity(op: str, dtype) -> np.ndarray:
    """Channel identity in the *value* dtype (delegates to the canonical
    ``core.plan.identity_of``): int blocks keep their integer dtype instead
    of being coerced to float32 — vertex ids >= 2^24 survive the packing."""
    from repro.core.plan import identity_of
    return np.asarray(identity_of(op, dtype))


def pack_edges(dst: np.ndarray, n_out: int, nb: int = 256,
               eb_align: int = 512):
    """Host-side, once per graph: sort edges by destination block and pad
    each block's edge list to a common multiple-of-``eb_align`` length.

    Returns (order, idx_local (n_blocks, Eb) int32 with -1 padding) where
    ``order`` permutes per-edge values into packed layout.  Fully
    vectorized (one stable argsort + a flat scatter; no per-block loop) —
    core/plan.py generalizes the same layout to the (M, ...) worker axis.
    """
    n_blocks = -(-n_out // nb)
    blk = dst // nb
    order = np.argsort(blk, kind="stable")
    counts = np.bincount(blk, minlength=n_blocks)
    eb = max(int(counts.max()), 1)
    eb = -(-eb // eb_align) * eb_align
    idx_local = np.full((n_blocks, eb), -1, np.int32)
    starts = np.zeros(n_blocks + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    sblk = blk[order]
    pos = np.arange(len(dst)) - starts[sblk]       # rank within block
    idx_local.reshape(-1)[sblk * eb + pos] = dst[order] - sblk * nb
    return order, idx_local


def pack_values(vals: np.ndarray, order: np.ndarray, idx_local: np.ndarray,
                op: str = "sum") -> np.ndarray:
    """Scatter per-edge values into the packed (n_blocks, Eb) layout
    (vectorized flat scatter aligned with ``pack_edges``).  The packed
    array keeps ``vals.dtype``; padding slots hold the op identity for
    that dtype (the kernel ignores them via idx == -1 either way)."""
    vals = np.asarray(vals)
    n_blocks, eb = idx_local.shape
    valid = idx_local.reshape(-1) >= 0
    if vals.ndim == 2:  # feature-blocked (E, F) payload
        out = np.full((n_blocks, eb, vals.shape[1]),
                      _identity(op, vals.dtype), vals.dtype)
        out.reshape(-1, vals.shape[1])[valid] = vals[order]
        return out
    out = np.full((n_blocks, eb), _identity(op, vals.dtype), vals.dtype)
    out.reshape(-1)[valid] = vals[order]
    return out


def segment_combine(packed_vals: jax.Array, packed_idx: jax.Array, op: str,
                    nb: int, n_out: int, use_kernel: bool = True,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Combine packed edge messages into (n_out,) destination values —
    or (n_out, F) when ``packed_vals`` carries a feature axis."""
    fn = segment_combine_blocks if use_kernel else segment_combine_blocks_ref
    out = fn(packed_vals, packed_idx, op, nb,
             **({"interpret": interpret} if use_kernel else {}))
    if out.ndim == 3:
        return out.reshape(-1, out.shape[2])[:n_out]
    return out.reshape(-1)[:n_out]


def segment_combine_rows(packed_vals: jax.Array, packed_idx: jax.Array,
                         rows: jax.Array, op: str, nb: int,
                         use_kernel: bool = True,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Block-subset entry point: combine only the ``rows`` subset of a
    packed layout, returning their (len(rows), nb) combined blocks.

    Rows are independent in ``segment_combine_blocks`` (each row reduces
    its own eb lanes into its own nb destination slots), so a subset's
    blocks combine bitwise-identically to their slice of the whole-array
    combine — the property the pipelined executor relies on to overlap
    one exchange chunk's ``all_to_all`` with the next chunk's local
    combine.  ``rows`` may be any (R_sub,) int index array (static or
    traced); out-of-range / repeated rows are the caller's business."""
    fn = segment_combine_blocks if use_kernel else segment_combine_blocks_ref
    return fn(packed_vals[rows], packed_idx[rows], op, nb,
              **({"interpret": interpret} if use_kernel else {}))

"""Pallas TPU kernel for sender-side message combining (the Ch_msg hot path).

TPU adaptation of the paper's per-message hash-table combiner (DESIGN.md §2):
a CPU combiner groups messages with a hash table — serial, pointer-chasing,
hostile to the VPU/MXU.  Here messages are pre-sorted by destination block
(host-side, once per graph) and each packed row combines its Eb edge lanes
into one Nb-wide destination block with a *dense* compare/reduce in VMEM:

    hit[e, n]  = (idx[e] == n)               (Eb x Nb per row)
    out[n]     = op_e  hit ? val[e] : identity

Every op (sum, min, max; float or int) is the same select-and-reduce on
the VPU — integer sums never reach the MXU, which has no int32 path on
v5e, and f32 sums add in full f32 (no bf16 matmul passes).  A grid step
takes an aligned tile of ``ROWS`` = 8 packed rows: an (8, Eb) value/index
block and an (8, Nb) output block, so both trailing block dims meet the
(8, 128) tiling rule (or equal the array's own dims).  The wrapper pads
the row count to a multiple of 8 with idx = -1 rows, which never hit.
Eb and Nb are the plan's row and block widths (``core/plan.py``: Eb in
[8, 512], Nb = 128 on TPU); at Eb=512, Nb=128 the (8, Eb, Nb) select
tile is 2 MB of f32, well inside the 16 MB scoped-VMEM default.

Feature-blocked (n_blocks, Eb, F) payloads run the same tile combine per
row over 8-feature chunks: the wrapper moves features ahead of edges,
(n_blocks, F, Eb), so each chunk is an (8, Eb) tile exactly like eight
scalar rows, and the (F, Nb) result is moved back after the call.

Dtype handling: float blocks use the finite sentinels NEG/POS as min/max
identities (VMEM-friendly; the plan layer maps them back to +-inf);
integer blocks use the dtype's iinfo bounds, which double as the exact
channel identities — id-carrying algorithms (Hash-Min, S-V) combine in
int32 so vertex ids above 2^24 stay exactly representable.

``interpret=None`` runs the compiled kernel on TPU and the Pallas
interpreter on any other backend (CPU tests); pass a bool to force one.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG = -3.0e38
POS = 3.0e38

# packed rows per grid step: the f32 sublane tile
ROWS = 8
# feature-tile width of the vector grid: one 128-lane register
FEAT_TILE = 128


def sentinels(dtype):
    """(min-identity, max-identity) used inside the combine blocks.

    Floats narrower than f32 (float16: max 65504) cannot represent the
    3e38 sentinels — they would overflow to inf and break the plan
    layer's sentinel -> +-inf remap — so sub-f32 floats fall back to
    their own finfo bounds (bfloat16 shares f32's exponent range and
    keeps the canonical NEG/POS).
    """
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return info.min, info.max
    info = jnp.finfo(dtype)
    if float(info.max) < POS:
        return float(info.min), float(info.max)
    return NEG, POS


def _combine_tile(vals, idx, op: str, nb: int):
    """(k, Eb) values + (k, Eb) block-local indices -> (k, Nb) combined
    rows.  Sums accumulate in f32 (floats) or int32 (ints)."""
    k, eb = vals.shape
    if op == "sum":
        acc = (jnp.int32 if jnp.issubdtype(vals.dtype, jnp.integer)
               else jnp.float32)
        vals = vals.astype(acc)
        fill, red = jnp.asarray(0, acc), jnp.sum
    else:
        neg, pos = sentinels(vals.dtype)
        fill = jnp.asarray(pos if op == "min" else neg, vals.dtype)
        red = jnp.min if op == "min" else jnp.max
    cols = jax.lax.broadcasted_iota(jnp.int32, (k, eb, nb), 2)
    hit = idx[:, :, None] == cols
    return red(jnp.where(hit, vals[:, :, None], fill), axis=1)


def _kernel(vals_ref, idx_ref, out_ref, *, op: str, nb: int):
    out_ref[...] = _combine_tile(vals_ref[...], idx_ref[...], op,
                                 nb).astype(out_ref.dtype)


def _kernel_vec(vals_ref, idx_ref, out_ref, *, op: str, nb: int):
    """Feature-major twin of ``_kernel``: vals (ROWS, ft, Eb), out
    (ROWS, ft, Nb).  Each row's index vector is shared by its features,
    so an 8-feature chunk combines as one (8, Eb) tile."""
    rows, ft, eb = vals_ref.shape
    n_fc = ft // ROWS

    def body(i, carry):  # a loop, not an unroll: one tile's VMEM live
        r = i // n_fc
        f0 = pl.multiple_of((i % n_fc) * ROWS, ROWS)
        idx = jnp.broadcast_to(idx_ref[pl.ds(r, 1), :], (ROWS, eb))
        out_ref[r, pl.ds(f0, ROWS), :] = _combine_tile(
            vals_ref[r, pl.ds(f0, ROWS), :], idx, op,
            nb).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows * n_fc, body, 0)


def segment_combine_blocks(vals: jax.Array, idx: jax.Array, op: str,
                           nb: int,
                           interpret: Optional[bool] = None) -> jax.Array:
    """vals: (n_blocks, Eb) or feature-blocked (n_blocks, Eb, F);
    idx: (n_blocks, Eb).  Returns (n_blocks, nb) / (n_blocks, nb, F)
    combined blocks.  idx entries are block-local destinations; padding
    idx = -1 (never hits).  Rows are independent: a row's result does
    not depend on which tile it lands in or on the padding rows."""
    interpret = resolve_interpret(interpret)
    n_blocks, eb = idx.shape
    n_pad = -(-n_blocks // ROWS) * ROWS
    if n_pad != n_blocks:
        idx = jnp.pad(idx, ((0, n_pad - n_blocks), (0, 0)),
                      constant_values=-1)
    if vals.ndim == 3:
        F = vals.shape[2]
        ft = min(-(-F // ROWS) * ROWS, FEAT_TILE)
        n_ft = -(-F // ft)
        Fp = n_ft * ft
        # features ahead of edges; pad rows and the tail feature tile
        # (features never mix, padding is sliced off after)
        vt = jnp.pad(jnp.swapaxes(vals, 1, 2),
                     ((0, n_pad - n_blocks), (0, Fp - F), (0, 0)))
        out = pl.pallas_call(
            functools.partial(_kernel_vec, op=op, nb=nb),
            grid=(n_pad // ROWS, n_ft),
            in_specs=[pl.BlockSpec((ROWS, ft, eb), lambda i, j: (i, j, 0)),
                      pl.BlockSpec((ROWS, eb), lambda i, j: (i, 0))],
            out_specs=pl.BlockSpec((ROWS, ft, nb), lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((n_pad, Fp, nb), vals.dtype),
            interpret=interpret,
            name="segment_combine",
        )(vt, idx)
        return jnp.swapaxes(out[:n_blocks, :F], 1, 2)
    if n_pad != n_blocks:
        vals = jnp.pad(vals, ((0, n_pad - n_blocks), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, op=op, nb=nb),
        grid=(n_pad // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, eb), lambda i: (i, 0)),
                  pl.BlockSpec((ROWS, eb), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, nb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, nb), vals.dtype),
        interpret=interpret,
        name="segment_combine",
    )(vals, idx)
    return out[:n_blocks] if n_pad != n_blocks else out

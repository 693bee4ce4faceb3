"""Message plans: destination-blocked layouts for the combine channels.

The dense Ch_msg path materializes a per-source-worker partial buffer of
shape (M, n_pad) — O(M^2 * n_loc) memory per superstep, which caps the
graph sizes one host can simulate.  A *message plan* is built once per
partitioned graph: every worker's outgoing edges are grouped by
(source worker, destination block) into fixed-width rows, generalizing
``pack_edges``/``pack_values`` (kernels/segment_combine/ops.py) to the
leading (M, ...) worker axis with fully vectorized numpy (no per-block
Python loops).  At superstep time the runtime gathers the per-edge values
into the packed layout and hands rows to ``segment_combine_blocks`` — the
purpose-built Pallas kernel — so the combine works block-by-block in VMEM
and the only O(n) buffers are the packed edges and the (n_blocks, nb)
output.

Blocking scheme: destination worker ``w`` owns local slots [0, n_loc);
block ``b`` of ``w`` covers local slots [b*nb, (b+1)*nb).  Global block id
= w * B_per_w + b, so a block never spans two workers and per-(source,
block) non-identity counts reproduce the paper's combined-message metric
exactly (distinct (source worker, destination vertex) pairs).

Oversized groups are split across multiple rows of the same segment; the
rows are merged with the combine op before counting, so splitting never
double-counts a destination.

Two runtime paths:

* ``combine_with_plan`` — static targets (the broadcast/mirror channels,
  whose edges are known at partition time): packed rows -> kernel ->
  segment merge -> global block scatter.
* ``combine_sorted``   — dynamic targets (S-V / MSF hooking writes, whose
  destinations are algorithm state): per-row sort + segmented reduce +
  one flat (n_pad,) scatter.  Same O(n_pad + M*K) memory bound, no
  precomputation possible.

Kernel dispatch: the Pallas kernel is compiled for real on TPU; on CPU the
block-layout jnp reference (same math, same layout) executes the plan, and
``set_kernel_mode('pallas')`` forces the kernel, which the backend check
in ``segment_combine_blocks`` runs interpreted off TPU (wiring tests).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segment_combine.kernel import (NEG, POS, sentinels,
                                                  segment_combine_blocks)
from repro.kernels.segment_combine.ref import segment_combine_blocks_ref

DEFAULT_NB = 128
DEFAULT_EB = 128


def default_nb() -> int:
    """Destination-block width: 128 on TPU (the lane width the kernel's
    hit-matrix wants); 32 on CPU, where narrower blocks shrink the
    (n_rows, nb) combined-block temp 2-3x with no layout downside."""
    return DEFAULT_NB if jax.default_backend() == "tpu" else 32

# "auto": Pallas kernel on TPU, block-layout jnp reference elsewhere.
# "pallas": force the kernel (interpret mode off-TPU). "ref": force jnp.
_KERNEL_MODE = "auto"


def set_kernel_mode(mode: str) -> None:
    global _KERNEL_MODE
    assert mode in ("auto", "pallas", "ref"), mode
    _KERNEL_MODE = mode


def kernel_mode() -> str:
    return _KERNEL_MODE


def identity_of(op: str, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray({"min": info.max, "max": info.min, "sum": 0}[op],
                           dtype)
    return jnp.asarray({"min": jnp.inf, "max": -jnp.inf, "sum": 0.0}[op],
                       dtype)


def scatter_op(op: str, buf, idx, vals):
    if op == "min":
        return buf.at[idx].min(vals)
    if op == "max":
        return buf.at[idx].max(vals)
    return buf.at[idx].add(vals)


def feat_mask(mask, values, lane_ndim: int):
    """Broadcast a lane mask over an optional trailing feature axis.

    The vector-payload convention everywhere: a value array is either
    lane-shaped (``lane_ndim`` axes, one value per lane — today's scalar
    contract, untouched) or carries ONE extra trailing feature axis
    ``(..., F)``.  Scalar inputs return ``mask`` unchanged, so the F=1
    bitwise-identity guarantee is structural, not numerical."""
    return mask if values.ndim == lane_ndim else mask[..., None]


def feat_shape(values, lane_ndim: int) -> tuple:
    """() for scalar payloads, (F,) for feature-blocked ones."""
    return tuple(values.shape[lane_ndim:])


def scatter_hits(n: int, idx, hits) -> jnp.ndarray:
    """(n,) bool "did at least one real message land here" from per-lane
    ``hits`` flags — the honest (mask-driven) message-accounting primitive:
    a destination counts when a real message was SENT to it, whatever its
    payload (a PageRank contribution of exactly 0.0 is still a message).
    ``idx`` lanes with ``hits`` False may point anywhere in range."""
    buf = jnp.zeros((n,), jnp.int32)
    return buf.at[jnp.where(hits, idx, 0)].max(hits.astype(jnp.int32)) > 0


@dataclasses.dataclass
class EdgePlan:
    """Packed destination-blocked layout of one edge set.

    Rows are (eb,)-wide slices of one (source worker, destination block)
    segment; ``row_gather`` indexes the *flattened* (M_src * E,) per-edge
    value array.
    """
    M_src: int
    M_dst: int
    n_loc: int
    nb: int
    eb: int
    B_per_w: int               # destination blocks per worker
    n_blocks: int              # M_dst * B_per_w
    n_segs: int
    n_rows: int
    # host-side numpy (NOT jnp): plans are built lazily, possibly while a
    # jit trace is active, and get closed over by many traced steps —
    # numpy constants are safe to reuse across traces, tracers are not.
    row_gather: np.ndarray     # (n_rows, eb) int32 -> flat edge index
    row_valid: np.ndarray      # (n_rows, eb) bool
    row_local: np.ndarray      # (n_rows, eb) int32 dst-in-block, pad -1
    row_seg: np.ndarray        # (n_rows,) int32 -> segment
    seg_blk: np.ndarray        # (n_segs,) int32 global block id
    seg_worker: np.ndarray     # (n_segs,) int32 source worker

    @property
    def packed_bytes(self) -> int:
        return self.n_rows * self.eb * 9 + self.n_rows * 4


def build_edge_plan(dst_worker: np.ndarray, dst_local: np.ndarray,
                    mask: np.ndarray, M_dst: int, n_loc: int,
                    nb: int = DEFAULT_NB,
                    eb: Optional[int] = None) -> EdgePlan:
    """dst_worker/dst_local/mask: (M_src, E) host arrays (padded layout).
    Vectorized: one argsort over the kept edges, no per-block loops.

    ``eb`` (row width) defaults to adapting to the segment-size
    distribution: the p90 segment size rounded up to a power of two in
    [8, DEFAULT_EB*4].  Narrow rows keep padding low on sparse segments
    (many workers, few edges per block); oversized segments simply span
    multiple rows, which the segment merge re-combines.  8 is the f32
    sublane minimum, so every choice stays TPU-tileable."""
    dst_worker = np.asarray(dst_worker)
    dst_local = np.asarray(dst_local)
    mask = np.asarray(mask)
    M_src, E = dst_worker.shape

    keep = mask.reshape(-1)
    flat_idx = np.flatnonzero(keep).astype(np.int64)
    src_w = flat_idx // max(E, 1)
    return _pack_edge_plan(flat_idx, src_w,
                           dst_worker.reshape(-1)[flat_idx],
                           dst_local.reshape(-1)[flat_idx],
                           M_src, M_dst, n_loc, nb, eb)


def build_edge_plan_flat(src_worker: np.ndarray, dst_worker: np.ndarray,
                         dst_local: np.ndarray, M_src: int, M_dst: int,
                         n_loc: int, nb: int = DEFAULT_NB,
                         eb: Optional[int] = None) -> EdgePlan:
    """CSR-layout twin of ``build_edge_plan``: flat (E,) edge arrays with
    explicit per-edge source workers, no padding mask.  ``row_gather``
    then indexes the flat (E,) per-edge value array directly — the CSR
    layout is destination-blockable without an intermediate padded
    unpack."""
    src_worker = np.asarray(src_worker, np.int64)
    flat_idx = np.arange(len(src_worker), dtype=np.int64)
    return _pack_edge_plan(flat_idx, src_worker,
                           np.asarray(dst_worker, np.int64),
                           np.asarray(dst_local, np.int64),
                           M_src, M_dst, n_loc, nb, eb)


def _pack_edge_plan(flat_idx: np.ndarray, src_w: np.ndarray,
                    dst_worker: np.ndarray, dst_local: np.ndarray,
                    M_src: int, M_dst: int, n_loc: int, nb: int,
                    eb: Optional[int]) -> EdgePlan:
    """Shared packer: per-kept-edge flat value index + (source worker,
    destination worker/local) -> destination-blocked rows."""
    B_per_w = max(-(-n_loc // nb), 1)
    n_blocks = M_dst * B_per_w
    blk = dst_worker * B_per_w + dst_local // nb
    loc_in_blk = dst_local % nb

    key = src_w * n_blocks + blk
    order = np.argsort(key, kind="stable")
    skey = key[order]
    n_kept = len(skey)

    if n_kept == 0:
        eb = eb or DEFAULT_EB
        return EdgePlan(M_src, M_dst, n_loc, nb, eb, B_per_w, n_blocks,
                        0, 0, np.zeros((0, eb), np.int32),
                        np.zeros((0, eb), bool),
                        np.zeros((0, eb), np.int32),
                        np.zeros((0,), np.int32),
                        np.zeros((0,), np.int32),
                        np.zeros((0,), np.int32))

    first = np.concatenate([[True], skey[1:] != skey[:-1]])
    seg_of = np.cumsum(first) - 1                       # per kept edge
    n_segs = int(seg_of[-1]) + 1
    seg_key = skey[first]
    seg_start = np.flatnonzero(first)
    seg_count = np.diff(np.append(seg_start, n_kept))
    pos = np.arange(n_kept) - seg_start[seg_of]         # rank within segment

    if eb is None:
        p90 = int(np.percentile(seg_count, 90))
        eb = 8
        while eb < p90 and eb < DEFAULT_EB * 4:
            eb *= 2

    seg_nrows = -(-seg_count // eb)
    seg_row0 = np.concatenate([[0], np.cumsum(seg_nrows)[:-1]])
    n_rows = int(seg_nrows.sum())
    row_of = seg_row0[seg_of] + pos // eb
    col_of = pos % eb

    row_gather = np.zeros((n_rows, eb), np.int32)
    row_valid = np.zeros((n_rows, eb), bool)
    row_local = np.full((n_rows, eb), -1, np.int32)
    slot = row_of * eb + col_of
    row_gather.reshape(-1)[slot] = flat_idx[order]
    row_valid.reshape(-1)[slot] = True
    row_local.reshape(-1)[slot] = loc_in_blk[order]

    row_seg = np.repeat(np.arange(n_segs, dtype=np.int32),
                        seg_nrows.astype(np.int64))
    return EdgePlan(
        M_src, M_dst, n_loc, nb, eb, B_per_w, n_blocks, n_segs, n_rows,
        row_gather, row_valid, row_local, row_seg,
        (seg_key % n_blocks).astype(np.int32),
        (seg_key // n_blocks).astype(np.int32))


def _combine_rows(packed: jnp.ndarray, row_local: jnp.ndarray, op: str,
                  nb: int) -> jnp.ndarray:
    """Dispatch one (n_rows, eb) -> (n_rows, nb) block combine."""
    mode = _KERNEL_MODE
    if mode == "auto":
        mode = "pallas" if jax.default_backend() == "tpu" else "ref"
    if mode == "ref":
        out = segment_combine_blocks_ref(packed, row_local, op, nb)
    else:
        out = segment_combine_blocks(packed, row_local, op, nb)
    # The kernel's float min/max identities are finite sentinels
    # (VMEM-friendly); map no-hit slots back to the channel identities so
    # the combined blocks compare exactly against the dense path.  Integer
    # blocks already use iinfo bounds == the channel identities, so the
    # id-carrying algorithms combine exactly in their integer dtype.
    # The thresholds come from sentinels(dtype): float16 blocks saturate
    # at +-65504, where the canonical 3e38 would overflow to inf and the
    # comparison could never fire.
    if jnp.issubdtype(packed.dtype, jnp.floating):
        neg, pos = sentinels(packed.dtype)
        if op == "min":
            out = jnp.where(out >= pos, jnp.inf, out)
        elif op == "max":
            out = jnp.where(out <= neg, -jnp.inf, out)
    return out


def combine_rows_subset(plan, flat_vals: jnp.ndarray, rows: jnp.ndarray,
                        rows_ok: jnp.ndarray, op: str) -> jnp.ndarray:
    """Combine one static subset of plan rows (a pipeline chunk): gather
    the rows' packed lanes and run the same kernel-dispatched block
    combine as the whole-plan path.  Rows are independent inside
    ``segment_combine_blocks``, so a chunk's blocks combine
    bitwise-identically to their slice of the full-plan combine.

    ``rows_ok`` masks padded chunk slots (their lanes combine to the op
    identity, so scattering them anywhere is harmless for min/max/sum).
    Works on both EdgePlan (host numpy fields) and the executor's
    TracedPlan (device arrays) — only ``row_gather``/``row_valid``/
    ``row_local``/``nb`` are read."""
    ident = identity_of(op, flat_vals.dtype)
    valid = rows_ok[:, None] & jnp.asarray(plan.row_valid)[rows]
    gathered = flat_vals[jnp.asarray(plan.row_gather)[rows]]
    packed = jnp.where(feat_mask(valid, gathered, 2), gathered, ident)
    rloc = jnp.where(valid, jnp.asarray(plan.row_local)[rows], -1)
    return _combine_rows(packed, rloc, op, plan.nb)


def plan_seg_hits(plan: EdgePlan, flat_hits: jnp.ndarray) -> jnp.ndarray:
    """(n_segs, nb) bool: did >= 1 real (masked-in) message land in each
    per-(source, block) destination slot?  The mask-driven twin of the
    value combine — counting by ``combined != identity`` silently drops
    genuine messages whose payload equals the identity.  Rides the same
    block-combine kernel as the values (op=max over 0/1 lanes)."""
    hitp = plan.row_valid & flat_hits[plan.row_gather]       # (n_rows, eb)
    rh = _combine_rows(hitp.astype(jnp.int32), plan.row_local, "max",
                       plan.nb)
    sh = jnp.zeros((plan.n_segs, plan.nb), jnp.int32)
    return sh.at[plan.row_seg].max(rh) > 0


def combine_with_plan(plan: EdgePlan, flat_vals: jnp.ndarray, op: str,
                      count_cross: bool = True,
                      log_of: Optional[np.ndarray] = None,
                      M_out: Optional[int] = None,
                      flat_hits: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Optional[Tuple]]:
    """Combine per-edge values (flattened (M_src*E,)) into a (M_dst, n_loc)
    inbox.  Returns (inbox, (msgs_combined, per_worker_combined) | None);
    the count is the paper's combined-message metric: distinct (source
    worker, destination vertex) pairs that received at least one real
    message (``flat_hits``, the runtime send mask — identity-valued real
    messages count too), destination owned by another worker.

    Plans built from a *split* partition key their segments by physical
    shard (combining runs per shard); ``log_of`` then maps shard ids back
    to logical workers — a message is cross iff it leaves the *logical*
    worker, and ``per_worker_combined`` is reported over the ``M_out``
    logical workers.
    """
    assert flat_vals.ndim in (1, 2), \
        "pass per-edge values flattened: (E,) or feature-blocked (E, F)"
    feat = feat_shape(flat_vals, 1)
    if plan.n_rows:
        assert int(plan.row_gather.max()) < flat_vals.shape[0], \
            "plan does not match this edge set"
    M_out = M_out if M_out is not None else plan.M_src
    ident = identity_of(op, flat_vals.dtype)
    if plan.n_rows == 0:
        inbox = jnp.full((plan.M_dst, plan.n_loc) + feat, ident,
                         flat_vals.dtype)
        if count_cross:
            return inbox, (jnp.zeros((), jnp.int32),
                           jnp.zeros((M_out,), jnp.int32))
        return inbox, None

    gathered = flat_vals[plan.row_gather]
    packed = jnp.where(feat_mask(plan.row_valid, gathered, 2), gathered,
                       ident)
    row_out = _combine_rows(packed, plan.row_local, op, plan.nb)

    seg_buf = jnp.full((plan.n_segs, plan.nb) + feat, ident,
                       flat_vals.dtype)
    seg_out = scatter_op(op, seg_buf, plan.row_seg, row_out)

    glob = jnp.full((plan.n_blocks, plan.nb) + feat, ident, flat_vals.dtype)
    glob = scatter_op(op, glob, plan.seg_blk, seg_out)
    inbox = glob.reshape((plan.M_dst, plan.B_per_w * plan.nb) + feat
                         )[:, :plan.n_loc]

    stats = None
    if count_cross:
        assert flat_hits is not None, \
            "count_cross=True needs the per-lane send mask (flat_hits)"
        seg_log = (plan.seg_worker if log_of is None
                   else np.asarray(log_of)[plan.seg_worker])
        owner = plan.seg_blk // plan.B_per_w
        cross = plan_seg_hits(plan, flat_hits) & (owner != seg_log)[:, None]
        msgs = cross.sum().astype(jnp.int32)
        per_worker = jnp.zeros((M_out,), jnp.int32).at[
            seg_log].add(cross.sum(axis=1).astype(jnp.int32))
        stats = (msgs, per_worker)
    return inbox, stats


# ---------------------------------------------------------------------------
# dynamic targets: sorted segmented combine (no precomputation possible)
# ---------------------------------------------------------------------------

def sorted_segments(targets: jnp.ndarray, values: jnp.ndarray,
                    mask: jnp.ndarray, op: str, n_pad: int):
    """Per-row sort + segmented reduce of runtime (R, K) target rows:
    the shared core of the sorted combine, used by both the single-device
    path below and the sharded executor (core/exec.py) so the combine and
    message-accounting rules live in exactly one place.

    Returns ``(real, seg_t, seg_val, seg_row, ident)``: for every live
    (row, distinct target) segment its validity, target, combined value,
    and source row."""
    ident = identity_of(op, values.dtype)
    feat = feat_shape(values, 2)
    R, K = targets.shape
    t = jnp.where(mask, targets, n_pad)          # sentinel sorts last
    order = jnp.argsort(t, axis=1)
    ts = jnp.take_along_axis(t, order, axis=1)
    vs = jnp.take_along_axis(
        jnp.where(feat_mask(mask, values, 2), values, ident),
        feat_mask(order, values, 2), axis=1)

    first = jnp.concatenate(
        [jnp.ones((R, 1), bool), ts[:, 1:] != ts[:, :-1]], axis=1)
    seg_id = (jnp.cumsum(first.reshape(-1)) - 1).astype(jnp.int32)
    seg_fn = {"min": jax.ops.segment_min, "max": jax.ops.segment_max,
              "sum": jax.ops.segment_sum}[op]
    seg_val = seg_fn(vs.reshape((R * K,) + feat), seg_id,
                     num_segments=R * K)
    seg_t = jax.ops.segment_min(ts.reshape(-1), seg_id, num_segments=R * K)
    rows = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[:, None], (R, K))
    seg_row = jax.ops.segment_min(rows.reshape(-1), seg_id,
                                  num_segments=R * K)
    live = jnp.zeros((R * K,), bool).at[seg_id].set(True)
    real = live & (seg_t < n_pad)
    return real, seg_t, seg_val, seg_row, ident


def combine_sorted(targets: jnp.ndarray, values: jnp.ndarray,
                   mask: jnp.ndarray, op: str, M: int, n_loc: int
                   ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Sender-side combine for runtime target arrays (M, K): sort each
    worker's targets, reduce duplicate targets with ``jax.ops.segment_*``,
    then one flat scatter into a single (n_pad,) buffer — never the dense
    (M, n_pad) partial.  Returns (inbox (M, n_loc), (msgs_combined,
    per_worker_combined)), combined counts identical to the dense path.
    """
    n_pad = M * n_loc
    feat = feat_shape(values, 2)
    real, seg_t, seg_val, seg_row, ident = sorted_segments(
        targets, values, mask, op, n_pad)

    # inbox: receiver applies the same associative op, so one flat scatter
    # of the per-segment combined values is exact.
    buf = jnp.full((n_pad,) + feat, ident, values.dtype)
    buf = scatter_op(op, buf, jnp.where(real, seg_t, 0),
                      jnp.where(feat_mask(real, seg_val, 1), seg_val, ident))
    inbox = buf.reshape((M, n_loc) + feat)

    # mask-driven crossness: a live segment IS >= 1 real message — never
    # test the combined value against the identity (a genuine payload can
    # equal it, e.g. a PageRank contribution of exactly 0.0 under sum)
    cross = real & (seg_t // n_loc != seg_row)
    msgs = cross.sum().astype(jnp.int32)
    per_worker = jnp.zeros((M,), jnp.int32).at[
        jnp.where(cross, seg_row, 0)].add(cross.astype(jnp.int32))
    return inbox, (msgs, per_worker)


def sort_by_worker_target(worker: jnp.ndarray, t: jnp.ndarray):
    """Two-pass stable sort of flat (E,) pairs by (worker, target) — no
    ``worker * n_pad + target`` composite key that could overflow int32.
    Returns (order, sorted worker, sorted target, first-of-segment mask);
    a segment is one distinct (worker, target) pair."""
    worker, t = jnp.asarray(worker), jnp.asarray(t)
    order1 = jnp.argsort(t, stable=True)
    order = order1[jnp.argsort(worker[order1], stable=True)]
    ws, ts = worker[order], t[order]
    first = jnp.concatenate(
        [jnp.ones((1,), bool), (ws[1:] != ws[:-1]) | (ts[1:] != ts[:-1])])
    return order, ws, ts, first


def sorted_segments_flat(targets: jnp.ndarray, values: jnp.ndarray,
                         mask: jnp.ndarray, src_worker: jnp.ndarray,
                         op: str, n_pad: int):
    """Flat-(E,) twin of ``sorted_segments``: sort by (worker, target),
    segmented reduce.  Returns ``(real, seg_t, seg_val, seg_w, ident)``
    — one entry per distinct live (source worker, target) pair.  Shared
    by the single-device path below and the sharded executor."""
    ident = identity_of(op, values.dtype)
    E = targets.shape[0]
    t = jnp.where(mask, targets, n_pad)          # sentinel sorts last
    order, ws, ts, first = sort_by_worker_target(src_worker, t)
    vs = jnp.where(feat_mask(mask, values, 1), values, ident)[order]

    seg_id = (jnp.cumsum(first) - 1).astype(jnp.int32)
    seg_fn = {"min": jax.ops.segment_min, "max": jax.ops.segment_max,
              "sum": jax.ops.segment_sum}[op]
    seg_val = seg_fn(vs, seg_id, num_segments=E)
    seg_t = jax.ops.segment_min(ts, seg_id, num_segments=E)
    seg_w = jax.ops.segment_min(ws, seg_id, num_segments=E)
    live = jnp.zeros((E,), bool).at[seg_id].set(True)
    real = live & (seg_t < n_pad)
    return real, seg_t, seg_val, seg_w, ident


def combine_sorted_flat(targets: jnp.ndarray, values: jnp.ndarray,
                        mask: jnp.ndarray, src_worker: jnp.ndarray,
                        op: str, M: int, n_loc: int,
                        log_of: Optional[np.ndarray] = None
                        ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray,
                                                      jnp.ndarray]]:
    """CSR twin of ``combine_sorted``: flat (E,) targets/values/mask with
    explicit per-edge source workers.  Sort by (worker, target), then a
    segmented reduce and one flat (n_pad,) scatter.  Combined counts are
    identical to the dense path (distinct non-identity (source worker,
    destination vertex) pairs, destination remote).

    With a split partition ``src_worker`` holds physical shard ids (the
    combining granularity) and ``log_of`` maps them to the (M,) logical
    workers for crossness and the per-worker report."""
    ident = identity_of(op, values.dtype)
    n_pad = M * n_loc
    feat = feat_shape(values, 1)
    if targets.shape[0] == 0:
        return (jnp.full((M, n_loc) + feat, ident, values.dtype),
                (jnp.zeros((), jnp.int32), jnp.zeros((M,), jnp.int32)))
    real, seg_t, seg_val, seg_w, ident = sorted_segments_flat(
        targets, values, mask, src_worker, op, n_pad)

    buf = jnp.full((n_pad,) + feat, ident, values.dtype)
    buf = scatter_op(op, buf, jnp.where(real, seg_t, 0),
                     jnp.where(feat_mask(real, seg_val, 1), seg_val, ident))
    inbox = buf.reshape((M, n_loc) + feat)

    seg_log = seg_w if log_of is None else jnp.asarray(log_of)[seg_w]
    # mask-driven crossness (see combine_sorted): live segment == real send
    cross = real & (seg_t // n_loc != seg_log)
    msgs = cross.sum().astype(jnp.int32)
    per_worker = jnp.zeros((M,), jnp.int32).at[
        jnp.where(cross, seg_log, 0)].add(cross.astype(jnp.int32))
    return inbox, (msgs, per_worker)


# ---------------------------------------------------------------------------
# plan cache keyed on the partitioned graph
# ---------------------------------------------------------------------------

def get_plan(pg, kind: str, nb: Optional[int] = None,
             eb: Optional[int] = None) -> EdgePlan:
    """Lazily build (and memoize on ``pg``) the plan for one edge set:
    ``eg`` (Ch_msg, non-mirrored sources), ``all`` (full adjacency), or
    ``mir`` (mirror fan-out, destinations local to the hosting worker)."""
    cache: Dict = pg.plan_cache
    nb = nb or default_nb()
    key = (kind, nb, eb)
    if key in cache:
        return cache[key]
    if kind not in ("eg", "all", "mir"):
        raise ValueError(f"unknown plan kind: {kind!r}")
    if getattr(pg, "layout", "padded") == "csr":
        # flat edges feed the packer directly: no padded unpack, no mask.
        # A split partition combines per *physical shard*: the plan's
        # source-worker axis becomes the shard id (callers fold stats back
        # to logical workers through pg.phys_log).
        split = getattr(pg, "phys_log", None) is not None
        M_src = pg.M_phys if split else pg.M
        if kind in ("eg", "all"):
            src = np.asarray(pg.eg_src if kind == "eg" else pg.all_src)
            dst = np.asarray(pg.eg_dst if kind == "eg" else pg.all_dst)
            sw = (np.asarray(pg.eg_pw if kind == "eg" else pg.all_pw)
                  if split else src // pg.n_loc)
            plan = build_edge_plan_flat(sw, dst // pg.n_loc,
                                        dst % pg.n_loc, M_src, pg.M,
                                        pg.n_loc, nb, eb)
        else:
            # mirror fan-out is local: source worker == hosting worker
            edst = np.asarray(pg.mir_edst)
            sw = (np.asarray(pg.mir_pw) if split else edst // pg.n_loc)
            plan = build_edge_plan_flat(sw, edst // pg.n_loc,
                                        edst % pg.n_loc, M_src, pg.M,
                                        pg.n_loc, nb, eb)
    elif kind == "eg":
        dst = np.asarray(pg.eg_dst)
        plan = build_edge_plan(dst // pg.n_loc, dst % pg.n_loc,
                               np.asarray(pg.eg_mask), pg.M, pg.n_loc,
                               nb, eb)
    elif kind == "all":
        dst = np.asarray(pg.all_dst)
        plan = build_edge_plan(dst // pg.n_loc, dst % pg.n_loc,
                               np.asarray(pg.all_mask), pg.M, pg.n_loc,
                               nb, eb)
    else:
        edst = np.asarray(pg.mir_edst)
        own = np.broadcast_to(np.arange(pg.M)[:, None], edst.shape)
        plan = build_edge_plan(own, edst, np.asarray(pg.mir_emask),
                               pg.M, pg.n_loc, nb, eb)
    cache[key] = plan
    return plan

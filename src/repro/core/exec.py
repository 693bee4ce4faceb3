"""Sharded superstep executor: the worker axis as a real device mesh.

On one device the engine simulates the paper's M workers as a batch axis;
this module makes the simulation *distributed*: ``jax.jit`` + ``shard_map``
over a 1-D device mesh (axis ``"w"``, built via ``launch/mesh.make_mesh``)
shards the worker axis across D devices (M % D == 0, m = M/D workers per
device).

``devices=(hosts, per_host)`` instead builds the 2-D ``("h", "w")`` mesh
(``launch/mesh.graph_mesh``) and every routed join above becomes
*hierarchical*: lanes first route to the device of their destination
column WITHIN the sender's host (one intra-host ``all_to_all`` over
``"w"``), that device op-combines everything it received by destination
(requests: deduplicates — the paper's Theorem-1/Theorem-3 reductions
applied per routing level), and only the combined residue crosses the
host axis (a second ``all_to_all`` over ``"h"``).  Cross-host volume is
therefore bounded by the post-combine residue, never the raw fan-out —
the property ``exchange_volume_report`` measures and the bench gates
pin.  Each leg carries its own cap derived per level from
``pair_counts`` (``_cap_hints_2d``), and the double-buffered pipeline
overlaps the *inter-host* leg, where collective latency actually
hurts.  The flat device id d = h*T + t is the row-major mesh order, so
owner arithmetic, stats, and parity against the 1-D path are unchanged
(min/max/int bitwise, stats integer-exact).

Every channel join is **destination-routed**: messages (and requests)
travel straight to the device that owns their destination via
``jax.lax.all_to_all`` with fixed per-destination-device lane caps, and
each device only ever materializes O(n/D + E/D)-sized buffers.  No join
replicates global state — there is no ``all_gather`` of the value shards
and no op-matched all-reduce over a global (n_pad,) scatter buffer
anywhere in the superstep (the paper's Theorems 1/3 bound per-worker
*communication*; replicating O(n) state per device would void exactly
that bound, and makes multi-host meshes untenable).

* Ch_msg, pallas/plan backend — the destination-blocked rows are packed
  *per device* at plan-build time; each device runs
  ``segment_combine_blocks`` on its rows, then the per-(source, block)
  segment partials are exchanged with ONE ``all_to_all``: the plan is
  blocked per destination device at stack time (static exchange indices,
  exact caps — runtime never overflows), and each device scatters the
  received segments into its local (m·B_per_w, nb) block range only.
* Ch_msg, dense backend / runtime-target scatters (S-V/MSF hooking) —
  the shared sorted segmented combine (``plan.sorted_segments*``) reduces
  duplicate (source, target) pairs locally, then the surviving segments
  are bucketed by destination device (``target // (m·n_loc)``) and
  exchanged in cap-sized ``all_to_all`` rounds: a psum'd remaining-lanes
  count drives extra rounds when a hot destination overflows the cap, so
  skew costs extra rounds, never correctness (and never a recompile).
  Receivers combine into a local (m·n_loc,) buffer.
* Ch_mir — mirror values are routed from the owner device to exactly the
  devices hosting fan-out edges for them, through a static fetch plan
  (per-device needed-value lists computed at graph-shard time; one
  ``all_to_all``).  The fan-out then runs on the local mirror edges.
* Ch_req — a real two-round trip: deduplicated requests route to the
  owner devices (cap-sized ``all_to_all`` rounds), owners answer from
  their local (m, n_loc) shard, responses route back.  The Theorem-3
  accounting (dedup, per-worker charges on requester and owner) is
  computed per device and psum-merged, identical to the reference counts.

Parity contract (pinned by tests/test_conformance.py's sharded axis and
``launch/shard_check.py``): for every algorithm x backend x layout,
``devices=D`` produces final state bitwise identical to the single-device
path for integer / min / max combines (sum combines like PageRank agree to
float round-off of the exchange reduction) and *every* ``msgs_*`` /
``per_worker_*`` statistic is integer-exact.

The flat CSR edge arrays are consumed per shard: each device receives the
contiguous slice of edges owned by its workers (edges are stored sorted by
owner), padded to the per-device maximum — O(E/D + M + n/D) per device,
never the padded (M, E_hot) wall.

Load balancing (``partition(..., balance="split")``): the partition's
*physical shards* (hot workers split by csr row-offset boundaries) become
the unit of device placement — ``device_edge_bounds`` packs contiguous
shard runs onto devices minimizing the bottleneck edge load, so device
boundaries are edge-balanced instead of worker-aligned.  A logical
worker's shards may then land on different devices while its vertex state
stays block-sharded, so the split executor (a) reads source values through
a static fetch plan (each device's needed source slots are known at
graph-shard time — never an all_gather of the state), (b) keys sender-side
combining and request dedup by physical shard (a shard never straddles
devices, so per-device accounting composes exactly), and (c) joins inboxes
through the routed exchange — min/max results stay bitwise identical to
the single-device split simulation and every stat integer-exact.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import bsp
from repro.core import cost_model
from repro.core import plan as planlib
from repro.core import spans
from repro.core.channels import _dedup_row, relay_values
from repro.core.plan import identity_of, scatter_op
from repro.launch import mesh as meshlib

AXIS = "w"
HAXIS = "h"

_MERGE = {"min": jnp.minimum, "max": jnp.maximum, "sum": jnp.add}

# Exchange chunks per superstep join when the double-buffered pipeline is
# on: each routed exchange is split into ~this many cap-sized chunks so
# chunk k's all_to_all can fly while chunk k-1 combines locally.  Two is
# the minimum that overlaps at all — exactly one exchange outstanding,
# matching the two-slot buffer — and each extra chunk deepens the
# pipeline at the price of another collective launch + kernel dispatch
# per join, which only pays off once collectives are asynchronous.
DEFAULT_PIPELINE_CHUNKS = 2


def broadcast_plan_kinds(backend: str, use_mirroring: bool = True) -> tuple:
    """The message plans the executor must pre-build (per device) for one
    ``channels.broadcast`` configuration — channel-layer knowledge kept in
    one place so the algorithms can't drift."""
    if backend != "pallas":
        return ()
    return ("eg", "mir") if use_mirroring else ("all",)


def _normalize_devices(devices):
    """``devices`` is an int (1-D worker mesh, today's executor) or an
    ``(hosts, per_host)`` pair (2-D hierarchical mesh).  Returns
    ``(D, hier)`` with ``hier`` either None or the ``(H, T)`` tuple —
    note (1, 8) and (8, 1) still select the hierarchical code paths
    (one axis is just size 1), which is exactly what the parity matrix
    exploits."""
    if isinstance(devices, (tuple, list)):
        H, T = int(devices[0]), int(devices[1])
        if H < 1 or T < 1:
            raise ValueError(f"bad (hosts, devices) mesh {devices!r}")
        return H * T, (H, T)
    return int(devices), None


def graph_mesh(devices):
    """Worker mesh: 1-D over ``devices`` devices, or the 2-D
    ``(hosts, per_host)`` mesh when a pair is given."""
    D, hier = _normalize_devices(devices)
    if D > len(jax.devices()):
        raise RuntimeError(
            f"requested {D} devices but only {len(jax.devices())} "
            f"are visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={D} before "
            f"importing jax (graph_run --devices does this for you)")
    if hier is not None:
        return meshlib.graph_mesh(*hier)
    return meshlib.make_mesh((D,), (AXIS,))


def _pad8(x: int) -> int:
    return max(8, -(-int(x) // 8) * 8)


def _cap_for(L: int, D: int, hint: Optional[int] = None) -> int:
    """Per-destination-device lane cap of one routed-exchange round.

    ``ceil(L/D)`` is exact for balanced traffic (one round); a hot
    destination just takes extra rounds.  ``hint`` — a static bound on the
    worst per-device-pair traffic (``PartitionedGraph.pair_counts``) —
    widens the cap up to 4x so statically-known skew still lands in one
    round without unbounding the (D, cap) buffer."""
    base = -(-L // D)
    cap = base if hint is None else max(base, min(int(hint), 4 * base))
    return min(_pad8(cap), _pad8(L))


# ---------------------------------------------------------------------------
# per-device plan stacking (pallas backend)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TracedPlan:
    """Device-local view of one per-device edge plan inside ``shard_map``.

    Row/segment counts are padded to the maximum across devices; dummy rows
    have ``row_valid`` all-False (they combine to identity and scatter into
    segment 0 harmlessly) and dummy segments are excluded from the exchange
    index lists, so they never contribute to inboxes or message counts.

    ``xseg``/``xval`` index MY segments per destination device (send side);
    ``rblk``/``rval`` give, per source device, the local destination block
    of each segment routed to me (receive side) — both built statically at
    stack time, so the all_to_all caps are exact.

    When the pipeline is on, the exchange is additionally blocked into
    ``n_chunks`` position-chunks of the xcap axis (same chunking on both
    sides of the all_to_all, so the pair caps stay exact).  Per chunk the
    tables list the rows feeding its segments (``crow``, chunk-local
    ``crow_seg`` remap) and the chunk-local exchange indices
    (``cxseg``/``cxval`` send, ``crblk``/``crval`` receive), so one
    chunk's rows can run ``segment_combine_blocks`` independently while
    another chunk's all_to_all is in flight.

    On a 2-D (host, device) mesh the exchange instead runs in two legs
    with an intermediate combine (the hierarchical tables below): leg 1
    routes my segments to the *column* of their destination device
    within my host (``x1seg``/``x1val``, all_to_all over the intra-host
    axis); the column device combines everything it received by global
    destination block (``iscat``/``ival`` -> ``n_iseg`` intermediate
    segments — the per-level Theorem-1 combine); leg 2 routes only the
    combined residue across the host axis (``x2seg``/``x2val`` send,
    ``r2blk``/``r2val`` receive at the owner).  With the pipeline on,
    the inter-host leg is position-chunked into ``hchunks`` static
    slices of the x2cap axis (where the overlap win actually lives)."""
    nb: int
    eb: int
    B_per_w: int
    n_blocks: int
    n_rows: int                # padded maximum
    n_segs: int                # padded maximum
    xcap: int                  # max segments routed between one device pair
    row_gather: jnp.ndarray    # (n_rows, eb) -> local flat edge index
    row_valid: jnp.ndarray     # (n_rows, eb)
    row_local: jnp.ndarray     # (n_rows, eb)
    row_seg: jnp.ndarray       # (n_rows,)
    seg_blk: jnp.ndarray       # (n_segs,) global block id
    seg_worker: jnp.ndarray    # (n_segs,) global source worker
    xseg: jnp.ndarray          # (D, xcap) my segment index per dest device
    xval: jnp.ndarray          # (D, xcap)
    rblk: jnp.ndarray          # (D, xcap) local dst block per source device
    rval: jnp.ndarray          # (D, xcap)
    # pipeline chunk tables (None when the pipeline is off):
    n_chunks: int = 1
    ccap: int = 0                          # exchange lanes per chunk
    cr: int = 0                            # max rows per chunk
    cs: int = 0                            # max segments per chunk
    crow: Optional[jnp.ndarray] = None     # (C, cr) row index
    crow_ok: Optional[jnp.ndarray] = None  # (C, cr)
    crow_seg: Optional[jnp.ndarray] = None  # (C, cr) chunk-local segment
    cxseg: Optional[jnp.ndarray] = None    # (C, D, ccap) chunk-local send
    cxval: Optional[jnp.ndarray] = None    # (C, D, ccap)
    crblk: Optional[jnp.ndarray] = None    # (C, D, ccap) local dst block
    crval: Optional[jnp.ndarray] = None    # (C, D, ccap)
    # hierarchical 2-D exchange tables (None on a 1-D mesh):
    x1cap: int = 0
    n_iseg: int = 0            # intermediate combined segments per device
    x2cap: int = 0
    hchunks: int = 1           # inter-host pipeline chunks
    x1seg: Optional[jnp.ndarray] = None    # (T, x1cap) my seg per dst col
    x1val: Optional[jnp.ndarray] = None    # (T, x1cap)
    iscat: Optional[jnp.ndarray] = None    # (T, x1cap) recv -> inter seg
    ival: Optional[jnp.ndarray] = None     # (T, x1cap)
    x2seg: Optional[jnp.ndarray] = None    # (H, x2cap) inter seg per host
    x2val: Optional[jnp.ndarray] = None    # (H, x2cap)
    r2blk: Optional[jnp.ndarray] = None    # (H, x2cap) local dst block
    r2val: Optional[jnp.ndarray] = None    # (H, x2cap)


def _device_plans(pg, D: int, kind: str, nb: int):
    """One EdgePlan per device covering that device's workers' edges, with
    *global* source-worker ids in ``seg_worker`` (message accounting) and
    *global* destination blocks (the exchange address space).  For a split
    partition the device slices follow the physical-shard bounds and
    ``seg_worker`` holds shard ids (combining granularity)."""
    M, n_loc = pg.M, pg.n_loc
    m = M // D
    split = _is_split(pg)
    dbounds = device_edge_bounds(pg, D) if split else None

    def build(d, eb):
        if pg.layout == "csr":
            M_src = pg.M_phys if split else M
            if kind in ("eg", "all"):
                src = np.asarray(pg.eg_src if kind == "eg" else pg.all_src)
                dst = np.asarray(pg.eg_dst if kind == "eg" else pg.all_dst)
                if split:
                    s, e = int(dbounds[kind][d]), int(dbounds[kind][d + 1])
                    pw = np.asarray(pg.eg_pw if kind == "eg"
                                    else pg.all_pw)
                    sw = pw[s:e]
                else:
                    off = pg.eg_off if kind == "eg" else pg.all_off
                    s, e = int(off[d * m]), int(off[(d + 1) * m])
                    sw = src[s:e] // n_loc
                return planlib.build_edge_plan_flat(
                    sw, dst[s:e] // n_loc, dst[s:e] % n_loc,
                    M_src, M, n_loc, nb, eb)
            edst = np.asarray(pg.mir_edst)
            if split:
                s, e = int(dbounds["mir"][d]), int(dbounds["mir"][d + 1])
                sw = np.asarray(pg.mir_pw)[s:e]
            else:
                s, e = int(pg.mir_eoff[d * m]), int(pg.mir_eoff[(d + 1) * m])
                sw = edst[s:e] // n_loc
            return planlib.build_edge_plan_flat(
                sw, edst[s:e] // n_loc, edst[s:e] % n_loc,
                M_src, M, n_loc, nb, eb)
        sl = slice(d * m, (d + 1) * m)
        if kind in ("eg", "all"):
            dst = np.asarray(pg.eg_dst if kind == "eg" else pg.all_dst)[sl]
            mask = np.asarray(pg.eg_mask if kind == "eg"
                              else pg.all_mask)[sl]
            p = planlib.build_edge_plan(dst // n_loc, dst % n_loc, mask,
                                        M, n_loc, nb, eb)
        else:
            edst = np.asarray(pg.mir_edst)[sl]
            own = np.broadcast_to(np.arange(d * m, (d + 1) * m)[:, None],
                                  edst.shape)
            p = planlib.build_edge_plan(own, edst,
                                        np.asarray(pg.mir_emask)[sl],
                                        M, n_loc, nb, eb)
        # build_edge_plan derives source workers from the (local) row index
        p.seg_worker = (p.seg_worker + d * m).astype(np.int32)
        return p

    plans = [build(d, None) for d in range(D)]
    eb = max(p.eb for p in plans)
    plans = [p if p.eb == eb else build(d, eb)
             for d, p in enumerate(plans)]
    return plans


def _stack_plans(plans, m: int, chunks: Optional[int] = None,
                 hier: Optional[Tuple[int, int]] = None):
    """Pad per-device plans to common row/segment counts, build the
    per-destination-device exchange index lists, and stack everything with
    a leading device axis.  Returns (static_meta, arrays_dict).

    ``chunks`` (the pipeline) additionally blocks the xcap axis into
    position-chunks and emits, per (device, chunk), the static row subset
    feeding that chunk's segments plus chunk-local segment/exchange
    remaps — the tables :func:`_combine_with_plan_sharded` walks to
    overlap chunk k's all_to_all with chunk k±1's local combines.

    ``hier=(H, T)`` (2-D mesh) additionally builds the two-leg exchange
    tables (see :class:`TracedPlan`): per destination *column* send lists,
    the intermediate combine-by-destination-block remap, and per
    destination *host* residue lists.  The pipeline then chunks the
    inter-host leg instead of the flat xcap axis."""
    D = len(plans)
    nb, eb = plans[0].nb, plans[0].eb
    bpd = m * plans[0].B_per_w               # destination blocks per device
    R = max(1, max(p.n_rows for p in plans))
    S = max(1, max(p.n_segs for p in plans))

    # destination-device blocking of the (real, un-padded) segments: the
    # routed exchange is fully static, so the caps are exact by
    # construction and the runtime never overflows them
    pair = {}
    xcap = 1
    for d, p in enumerate(plans):
        dd = (p.seg_blk // bpd if p.n_segs
              else np.zeros(0, np.int64))
        for d2 in range(D):
            sel = np.flatnonzero(dd == d2)
            pair[(d, d2)] = sel
            xcap = max(xcap, len(sel))

    a = {
        "row_gather": np.zeros((D, R, eb), np.int32),
        "row_valid": np.zeros((D, R, eb), bool),
        "row_local": np.full((D, R, eb), -1, np.int32),
        "row_seg": np.zeros((D, R), np.int32),
        "seg_blk": np.zeros((D, S), np.int32),
        "seg_worker": np.zeros((D, S), np.int32),
        "xseg": np.zeros((D, D, xcap), np.int32),
        "xval": np.zeros((D, D, xcap), bool),
        "rblk": np.zeros((D, D, xcap), np.int32),
        "rval": np.zeros((D, D, xcap), bool),
    }
    for d, p in enumerate(plans):
        a["row_gather"][d, :p.n_rows] = p.row_gather
        a["row_valid"][d, :p.n_rows] = p.row_valid
        a["row_local"][d, :p.n_rows] = p.row_local
        a["row_seg"][d, :p.n_rows] = p.row_seg
        a["seg_blk"][d, :p.n_segs] = p.seg_blk
        a["seg_worker"][d, :p.n_segs] = p.seg_worker
    for (d, d2), sel in pair.items():
        c = len(sel)
        a["xseg"][d, d2, :c] = sel
        a["xval"][d, d2, :c] = True
        a["rblk"][d2, d, :c] = plans[d].seg_blk[sel] - d2 * bpd
        a["rval"][d2, d, :c] = True
    meta = {"nb": nb, "eb": eb, "B_per_w": plans[0].B_per_w,
            "n_blocks": plans[0].n_blocks, "n_rows": R, "n_segs": S,
            "xcap": xcap}
    if hier is not None:
        meta.update(_hier_plan_tables(plans, a, D, bpd, *hier,
                                      chunks=chunks))
    elif chunks:
        meta.update(_chunk_plans(plans, pair, a, D, bpd, xcap, chunks))
    return meta, a


def _hier_plan_tables(plans, a, D: int, bpd: int, H: int, T: int,
                      chunks: Optional[int] = None):
    """Two-leg static exchange tables for a 2-D (H, T) mesh.

    Leg 1 (intra-host, axis ``"w"``): device (h, t1) sends each real
    segment to the device of its destination *column* t2 within its own
    host.  The intermediate device (h, t2) combines everything it
    received by global destination block — two segments from different
    senders aimed at the same block merge *before* crossing the host
    axis (the Theorem-1 combine applied per level).  Leg 2 (inter-host,
    axis ``"h"``): only the combined residue travels to the owner host.
    All index lists are position-aligned across the all_to_all (lane
    (t1, j) at the receiver is lane j of sender (h, t1)), so the caps
    are exact by construction and the runtime never overflows."""
    # leg-1 send lists: my segments by destination column (ascending
    # segment order — the canonical lane order both sides agree on)
    x1list = {}
    x1cap = 1
    for d, p in enumerate(plans):
        dd = (p.seg_blk // bpd if p.n_segs else np.zeros(0, np.int64))
        for t2 in range(T):
            sel = np.flatnonzero(dd % T == t2)
            x1list[(d, t2)] = sel
            x1cap = max(x1cap, len(sel))

    # intermediate combine: per device (h, t2), the distinct destination
    # blocks among its received lanes, and each lane's remap into them
    iblocks = {}
    n_iseg = 1
    for h in range(H):
        for t2 in range(T):
            i = h * T + t2
            gbs = [plans[h * T + t1].seg_blk[x1list[(h * T + t1, t2)]]
                   for t1 in range(T)]
            allg = (np.concatenate(gbs) if gbs else np.zeros(0, np.int64))
            iblocks[i] = np.unique(allg)
            n_iseg = max(n_iseg, len(iblocks[i]))

    # leg-2 residue lists: intermediate segments by destination host
    x2list = {}
    x2cap = 1
    for i in range(D):
        dh = (iblocks[i] // bpd) // T
        for h2 in range(H):
            sel = np.flatnonzero(dh == h2)
            x2list[(i, h2)] = sel
            x2cap = max(x2cap, len(sel))

    x1seg = np.zeros((D, T, x1cap), np.int32)
    x1val = np.zeros((D, T, x1cap), bool)
    iscat = np.zeros((D, T, x1cap), np.int32)
    ival = np.zeros((D, T, x1cap), bool)
    x2seg = np.zeros((D, H, x2cap), np.int32)
    x2val = np.zeros((D, H, x2cap), bool)
    r2blk = np.zeros((D, H, x2cap), np.int32)
    r2val = np.zeros((D, H, x2cap), bool)
    for h in range(H):
        for t2 in range(T):
            i = h * T + t2
            for t1 in range(T):
                s = h * T + t1
                sel = x1list[(s, t2)]
                c = len(sel)
                x1seg[s, t2, :c] = sel
                x1val[s, t2, :c] = True
                iscat[i, t1, :c] = np.searchsorted(
                    iblocks[i], plans[s].seg_blk[sel])
                ival[i, t1, :c] = True
            for h2 in range(H):
                sel = x2list[(i, h2)]
                c = len(sel)
                o = h2 * T + t2
                x2seg[i, h2, :c] = sel
                x2val[i, h2, :c] = True
                r2blk[o, h, :c] = iblocks[i][sel] - o * bpd
                r2val[o, h, :c] = True
    a.update(x1seg=x1seg, x1val=x1val, iscat=iscat, ival=ival,
             x2seg=x2seg, x2val=x2val, r2blk=r2blk, r2val=r2val)
    return {"x1cap": x1cap, "n_iseg": n_iseg, "x2cap": x2cap,
            "hchunks": max(1, min(int(chunks or 1), x2cap))}


def _chunk_plans(plans, pair, a, D: int, bpd: int, xcap: int, chunks: int):
    """Pipeline chunk tables (see :func:`_stack_plans`).  Chunk c covers
    positions [c*ccap, (c+1)*ccap) of every pair's exchange list — the
    same position window on sender and receiver, so a chunk's all_to_all
    caps stay exact by construction.  Every real segment lands in exactly
    one chunk (its position in its destination-device list), hence every
    real row in exactly one chunk's row table: the chunks partition the
    local combine work."""
    ccap = max(1, -(-xcap // max(int(chunks), 1)))
    C = -(-xcap // ccap)

    # collect per (device, chunk): segment list (in d2-major position
    # order), row list, chunk-local remaps
    rows_dc, segs_dc = {}, {}
    for d, p in enumerate(plans):
        row_seg = p.row_seg            # sorted ascending by construction
        for c in range(C):
            seg_list = []              # (d2, j, seg) in collection order
            row_list = []
            row_cseg = []
            for d2 in range(D):
                sel = pair[(d, d2)][c * ccap:(c + 1) * ccap]
                for j, s in enumerate(sel):
                    local = len(seg_list)
                    seg_list.append((d2, j, int(s)))
                    lo = np.searchsorted(row_seg, s, "left")
                    hi = np.searchsorted(row_seg, s, "right")
                    row_list.extend(range(int(lo), int(hi)))
                    row_cseg.extend([local] * int(hi - lo))
            segs_dc[(d, c)] = seg_list
            rows_dc[(d, c)] = (row_list, row_cseg)

    CR = max(1, max(len(r) for r, _ in rows_dc.values()))
    CS = max(1, max(len(s) for s in segs_dc.values()))
    crow = np.zeros((D, C, CR), np.int32)
    crow_ok = np.zeros((D, C, CR), bool)
    crow_seg = np.zeros((D, C, CR), np.int32)
    cxseg = np.zeros((D, C, D, ccap), np.int32)
    cxval = np.zeros((D, C, D, ccap), bool)
    crblk = np.zeros((D, C, D, ccap), np.int32)
    crval = np.zeros((D, C, D, ccap), bool)
    for (d, c), (row_list, row_cseg) in rows_dc.items():
        k = len(row_list)
        crow[d, c, :k] = row_list
        crow_ok[d, c, :k] = True
        crow_seg[d, c, :k] = row_cseg
        for local, (d2, j, s) in enumerate(segs_dc[(d, c)]):
            cxseg[d, c, d2, j] = local
            cxval[d, c, d2, j] = True
            crblk[d2, c, d, j] = plans[d].seg_blk[s] - d2 * bpd
            crval[d2, c, d, j] = True
    a.update(crow=crow, crow_ok=crow_ok, crow_seg=crow_seg,
             cxseg=cxseg, cxval=cxval, crblk=crblk, crval=crval)
    return {"n_chunks": C, "ccap": ccap, "cr": CR, "cs": CS}


# ---------------------------------------------------------------------------
# static fetch plans: route known value sets owner -> consumer devices
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TracedFetch:
    """Device-local view of a static fetch plan: this device's needed
    remote/local values arrive as a compact (n_need,) array through ONE
    exchange (consumers' needed-slot lists are static, so the per-pair
    caps are exact).

    On a 2-D (host, device) mesh the plan instead runs in two legs
    through a per-host *gateway*: the owner (h_o, t) sends each value
    ONCE per consuming host — to device (h_c, t), the consuming host's
    gateway for column t (leg A, inter-host axis) — and the gateway
    fans it out to the consumers within its host (leg B, intra-host
    axis).  That is the paper's Theorem-1 mirror bound applied per
    routing level: the cross-host cost of a value is min(H, #consuming
    hosts), never #consuming devices."""
    n_need: int                # padded compact-array length
    cap: int = 0               # flat: max slots between one device pair
    send_slot: Optional[jnp.ndarray] = None  # (D, cap) LOCAL slot, -1 pad
    recv_pos: Optional[jnp.ndarray] = None   # (D, cap) compact pos, -1
    # hierarchical (2-D) tables:
    n_gw: int = 0              # gateway buffer length
    cap_a: int = 0             # max slots owner -> gateway (inter-host)
    cap_b: int = 0             # max slots gateway -> consumer (intra-host)
    a_send: Optional[jnp.ndarray] = None   # (H, cap_a) LOCAL slot, -1
    a_recv: Optional[jnp.ndarray] = None   # (H, cap_a) gateway pos, -1
    b_send: Optional[jnp.ndarray] = None   # (T, cap_b) gateway pos, -1
    b_recv: Optional[jnp.ndarray] = None   # (T, cap_b) compact pos, -1


def _build_fetch_plan(need_lists, D: int, loc_n: int,
                      hier: Optional[Tuple[int, int]] = None):
    """``need_lists``: per-device sorted unique GLOBAL slot ids (host
    numpy).  Owner of slot g is ``g // loc_n``.  Returns (meta, stacked
    host arrays) for :class:`TracedFetch` (two-leg gateway tables when
    ``hier=(H, T)``)."""
    n_need = max(1, max((len(x) for x in need_lists), default=1))
    if hier is not None:
        return _build_fetch_plan_hier(need_lists, loc_n, *hier, n_need)
    cap = 1
    pair = {}
    for d, need in enumerate(need_lists):
        need = np.asarray(need, np.int64)
        bounds = np.searchsorted(need, np.arange(D + 1) * loc_n)
        for s in range(D):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            pair[(s, d)] = (need[lo:hi], np.arange(lo, hi))
            cap = max(cap, hi - lo)
    send_slot = np.full((D, D, cap), -1, np.int32)
    recv_pos = np.full((D, D, cap), -1, np.int32)
    for (s, d), (slots, pos) in pair.items():
        c = len(slots)
        send_slot[s, d, :c] = slots - s * loc_n
        recv_pos[d, s, :c] = pos
    meta = {"cap": cap, "n_need": n_need}
    return meta, {"send_slot": send_slot, "recv_pos": recv_pos}


def _build_fetch_plan_hier(need_lists, loc_n: int, H: int, T: int,
                           n_need: int):
    """Two-leg fetch tables (see :class:`TracedFetch`).  The gateway of
    column t in host h_c is device (h_c, t): it receives, over the host
    axis, every slot owned by column-t devices that ANY device of host
    h_c needs (deduplicated per host — the per-level combine), then
    distributes within the host."""
    D = H * T
    # gateway slot sets: gw_set[(h_c, t)] = sorted unique slots needed by
    # host h_c whose owner device sits in column t
    gw_set = {}
    n_gw = 1
    for hc in range(H):
        lists = [np.asarray(need_lists[hc * T + t], np.int64)
                 for t in range(T)]
        host_need = (np.unique(np.concatenate(lists)) if lists
                     else np.zeros(0, np.int64))
        own_col = (host_need // loc_n) % T
        for to in range(T):
            gw_set[(hc, to)] = host_need[own_col == to]
            n_gw = max(n_gw, len(gw_set[(hc, to)]))

    cap_a = 1
    a_pairs = {}
    for (hc, to), s in gw_set.items():
        owner_host = s // (loc_n * T)
        bounds = np.searchsorted(owner_host, np.arange(H + 1))
        for ho in range(H):
            lo, hi = int(bounds[ho]), int(bounds[ho + 1])
            a_pairs[(ho, hc, to)] = (s[lo:hi], np.arange(lo, hi))
            cap_a = max(cap_a, hi - lo)
    cap_b = 1
    b_pairs = {}
    for hc in range(H):
        for tc in range(T):
            need = np.asarray(need_lists[hc * T + tc], np.int64)
            own_col = (need // loc_n) % T
            for to in range(T):
                sel = np.flatnonzero(own_col == to)
                gpos = np.searchsorted(gw_set[(hc, to)], need[sel])
                b_pairs[(to, tc, hc)] = (gpos, sel)
                cap_b = max(cap_b, len(sel))

    a_send = np.full((D, H, cap_a), -1, np.int32)
    a_recv = np.full((D, H, cap_a), -1, np.int32)
    for (ho, hc, to), (slots, pos) in a_pairs.items():
        c = len(slots)
        a_send[ho * T + to, hc, :c] = slots - (ho * T + to) * loc_n
        a_recv[hc * T + to, ho, :c] = pos
    b_send = np.full((D, T, cap_b), -1, np.int32)
    b_recv = np.full((D, T, cap_b), -1, np.int32)
    for (to, tc, hc), (gpos, pos) in b_pairs.items():
        c = len(gpos)
        b_send[hc * T + to, tc, :c] = gpos
        b_recv[hc * T + tc, to, :c] = pos
    meta = {"n_need": n_need, "n_gw": n_gw, "cap_a": cap_a,
            "cap_b": cap_b}
    return meta, {"a_send": a_send, "a_recv": a_recv,
                  "b_send": b_send, "b_recv": b_recv}


@spans.scope(spans.EXCHANGE)
def _fetch_planned(sg, fp: TracedFetch, flat_vals: jnp.ndarray, fill
                   ) -> jnp.ndarray:
    """Run one static fetch plan: returns my compact (n_need,) value
    array.  ``flat_vals`` is my local (m_loc*n_loc,) owner-side array.
    On a 2-D mesh the value rides the two-leg gateway route — one
    inter-host lane per (slot, consuming host), then intra-host
    fan-out.  ``flat_vals`` may carry a trailing feature axis — the
    (lanes, F) block rides the same route (``all_to_all`` splits axis 0,
    the scatter indices address axis 0)."""
    n = flat_vals.shape[0]
    feat = planlib.feat_shape(flat_vals, 1)
    if fp.a_send is not None:
        ga = flat_vals[jnp.clip(fp.a_send, 0, n - 1)]
        send_a = jnp.where(
            planlib.feat_mask(fp.a_send >= 0, ga, fp.a_send.ndim), ga, fill)
        recv_a = jax.lax.all_to_all(send_a, HAXIS, 0, 0)
        gidx = jnp.where(fp.a_recv >= 0, fp.a_recv, fp.n_gw)
        gw = jnp.full((fp.n_gw + 1,) + feat, fill, flat_vals.dtype
                      ).at[gidx].set(recv_a)[:-1]
        gb = gw[jnp.clip(fp.b_send, 0, fp.n_gw - 1)]
        send_b = jnp.where(
            planlib.feat_mask(fp.b_send >= 0, gb, fp.b_send.ndim), gb, fill)
        recv = jax.lax.all_to_all(send_b, AXIS, 0, 0)
        idx = jnp.where(fp.b_recv >= 0, fp.b_recv, fp.n_need)
    else:
        gs = flat_vals[jnp.clip(fp.send_slot, 0, n - 1)]
        send = jnp.where(
            planlib.feat_mask(fp.send_slot >= 0, gs, fp.send_slot.ndim),
            gs, fill)
        recv = jax.lax.all_to_all(send, sg.axis, 0, 0)
        idx = jnp.where(fp.recv_pos >= 0, fp.recv_pos, fp.n_need)
    buf = jnp.full((fp.n_need + 1,) + feat, fill, flat_vals.dtype)
    return buf.at[idx].set(recv)[:-1]


# ---------------------------------------------------------------------------
# host-side graph sharding
# ---------------------------------------------------------------------------

def csr_device_bounds(off: np.ndarray, M: int, D: int) -> np.ndarray:
    """(D+1,) edge offsets at device boundaries of a (M+1,) worker csr."""
    m = M // D
    return np.asarray(off)[np.arange(0, M + 1, m)]


def _is_split(pg) -> bool:
    return getattr(pg, "phys_log", None) is not None


def device_edge_bounds(pg, devices) -> Dict[str, np.ndarray]:
    """Per-device (D+1,) edge bounds for each csr edge set (``devices``
    an int or an ``(H, T)`` pair — bounds follow the flat device order).

    Default partitions place boundaries at worker multiples (m = M/D
    workers per device).  Split partitions place them between *physical
    shards*, packed contiguously to minimize the bottleneck per-device
    eg+mir edge load (``"phys"`` holds the shard-index bounds)."""
    D, _ = _normalize_devices(devices)
    if _is_split(pg):
        loads = np.diff(pg.phys_eg_off) + np.diff(pg.phys_mir_off)
        pb = cost_model.contiguous_bounds(loads, D)
        return {"phys": pb,
                "eg": np.asarray(pg.phys_eg_off)[pb],
                "all": np.asarray(pg.phys_all_off)[pb],
                "mir": np.asarray(pg.phys_mir_off)[pb]}
    return {"phys": None,
            "eg": csr_device_bounds(pg.eg_off, pg.M, D),
            "all": csr_device_bounds(pg.all_off, pg.M, D),
            "mir": csr_device_bounds(pg.mir_eoff, pg.M, D)}


def device_edge_loads(pg, devices) -> np.ndarray:
    """(D,) per-device superstep edge load (Ch_msg + mirror fan-out) the
    mesh placement yields — the number the bench-balance gate watches."""
    b = device_edge_bounds(pg, devices)
    return np.diff(b["eg"]) + np.diff(b["mir"])


def crossness_report(pg, devices=None) -> Dict[str, float]:
    """Static locality accounting from the partition's ``pair_counts``
    matrix: the fraction of combined messages (distinct (source worker,
    destination vertex) pairs — exactly what one full-broadcast
    superstep puts on the wire) that crosses a worker, device, or host
    boundary.  This is the objective ``balance="edges+refine"``
    descends, and it is honest by construction: the cross-worker count
    equals the measured ``msgs_combined`` of a full first superstep
    with mirroring off (pinned in tests).

    Devices map to uniform worker blocks of m = M/D (the state
    sharding); a hierarchical ``(H, T)`` mesh adds host blocks of M/H.
    Split partitions pack *physical* shards onto devices, so their
    device/host rows here are the logical-block approximation.
    """
    pc = np.asarray(pg.pair_counts, np.int64)
    M = pg.M
    total = int(pc.sum())

    def _frac(cross):
        return float(cross) / total if total else 0.0

    cross_w = total - int(np.trace(pc))
    rep = {"total": total, "cross_worker": cross_w,
           "cross_worker_frac": _frac(cross_w)}
    if devices is not None:
        D, hier = _normalize_devices(devices)
        if M % D:
            raise ValueError(f"M={M} must divide over D={D} devices")
        m = M // D
        blocks = pc.reshape(D, m, D, m).sum(axis=(1, 3))
        cross_d = total - int(np.trace(blocks))
        rep.update(D=D, cross_device=cross_d,
                   cross_device_frac=_frac(cross_d))
        if hier is not None:
            H, T = hier
            hb = blocks.reshape(H, T, H, T).sum(axis=(1, 3))
            cross_h = total - int(np.trace(hb))
            rep.update(H=H, cross_host=cross_h,
                       cross_host_frac=_frac(cross_h))
    return rep


def _pad_device_slices(arr: np.ndarray, bounds: np.ndarray, pad_row):
    """Slice a flat (E,) array at ``bounds`` into (D, cap) with per-device
    padding values ``pad_row[d]``; also returns the validity mask."""
    D = len(bounds) - 1
    counts = np.diff(bounds)
    cap = max(1, int(counts.max()))
    out = np.empty((D, cap), arr.dtype)
    valid = np.zeros((D, cap), bool)
    for d in range(D):
        c = int(counts[d])
        out[d, :c] = arr[bounds[d]:bounds[d + 1]]
        out[d, c:] = pad_row[d]
        valid[d, :c] = True
    return out, valid


def _cap_hint(pg, D: int) -> Optional[int]:
    """Static per-device-pair distinct-target bound from the partition's
    (M, M) worker-pair message-count matrix — the initial cap the routed
    edge-shaped exchanges use (None when unavailable, e.g. split bounds
    don't align with worker blocks)."""
    pc = getattr(pg, "pair_counts", None)
    if pc is None or _is_split(pg):
        return None
    m = pg.M // D
    blocks = pc.reshape(D, m, D, m).sum(axis=(1, 3))
    return int(blocks.max())


def _cap_hints_2d(pg, D: int, H: int, T: int
                  ) -> Tuple[Optional[int], Optional[int]]:
    """Level-aware cap hints for the 2-D mesh — the flat per-device-pair
    bound silently under-caps a hierarchical exchange (a column device
    funnels a whole host's traffic to T columns, and an intermediate
    device funnels T senders' residue to H hosts), so each leg gets its
    own bound from ``pair_counts``:

    * intra-host leg: worst (source device, destination column) traffic
      — destination hosts folded together;
    * inter-host leg: worst (source host, destination host, column)
      traffic — the pre-combine bound on the residue an intermediate
      device can route to one host (the combine only shrinks it).
    """
    pc = getattr(pg, "pair_counts", None)
    if pc is None or _is_split(pg):
        return None, None
    m = pg.M // D
    blocks = pc.reshape(D, m, D, m).sum(axis=(1, 3))
    hint_w = int(blocks.reshape(D, H, T).sum(axis=1).max())
    hint_h = int(blocks.reshape(H, T, H, T).sum(axis=1).max())
    return hint_w, hint_h


def _sharded_plan(pg, D: int, hier: Optional[Tuple[int, int]], kind: str,
                  chunks: Optional[int]):
    """The stacked device plan of one kind, ``(meta, arrays)`` as
    :func:`_stack_plans` returns it, built once per partitioned graph and
    device layout and memoized on ``pg.plan_cache`` under
    ``("sharded", kind, D, hier, nb, chunks)`` (``get_plan``'s keys are
    ``(kind, nb, eb)`` triples, so the two never collide).  The cached
    arrays are read-only and shared by every later call; callers get a
    copy of the meta, which they insert into their own."""
    nb = planlib.default_nb()
    key = ("sharded", kind, D, hier, nb, chunks)
    if key not in pg.plan_cache:
        pmeta, parrs = _stack_plans(_device_plans(pg, D, kind, nb),
                                    pg.M // D, chunks=chunks, hier=hier)
        for v in parrs.values():
            v.flags.writeable = False
        pg.plan_cache[key] = (pmeta, parrs)
    pmeta, parrs = pg.plan_cache[key]
    return dict(pmeta), parrs


@spans.traced(spans.SHARD_GRAPH)
def _shard_graph(pg, devices, plan_kinds: Sequence[str],
                 pipeline: bool = False,
                 pipeline_chunks: Optional[int] = None):
    """Build the device-stacked array pytree + matching PartitionSpecs.
    ``devices`` is an int (1-D mesh) or an ``(H, T)`` pair (2-D
    hierarchical mesh; the flat device order d = h*T + t matches the
    row-major mesh flattening, so every flat table below stays valid).
    The message plan of each of ``plan_kinds`` comes from
    :func:`_sharded_plan`, cached on ``pg.plan_cache`` per (kind, D,
    hier, nb, pipeline chunks); the shard arrays are built anew on every
    call."""
    D, hier = _normalize_devices(devices)
    M, n_loc = pg.M, pg.n_loc
    m = M // D
    loc_n = m * n_loc
    split = _is_split(pg)
    # chunking exists to overlap the collective with the local combine;
    # on a 1-device mesh the all_to_all is a local transpose, so the
    # extra kernel dispatches would be pure overhead — default the chunk
    # count to 1 there (an explicit pipeline_chunks still forces it)
    chunks = ((pipeline_chunks
               or (DEFAULT_PIPELINE_CHUNKS if D > 1 else 1))
              if pipeline else None)
    arrays: Dict = {"vmask": pg.vmask, "deg": pg.deg,
                    "mir_ids": pg.mir_ids, "mir_nworkers": pg.mir_nworkers}
    specs: Dict = {"vmask": P(AXIS), "deg": P(AXIS),
                   "mir_ids": P(), "mir_nworkers": P()}
    hint_w, hint_h = _cap_hints_2d(pg, D, *hier) if hier else (None, None)
    meta = {"M": M, "n_loc": n_loc, "D": D, "m_loc": m, "n": pg.n,
            "tau": pg.tau, "layout": pg.layout, "split": split,
            "hier": hier, "cap_hint": _cap_hint(pg, D),
            "cap_hint_w": hint_w, "cap_hint_h": hint_h, "plan_meta": {},
            "fetch_meta": {}, "pipeline": pipeline,
            "pipeline_chunks": chunks or 1}

    def add_fetch(name, need_lists):
        fmeta, farr = _build_fetch_plan(need_lists, D, loc_n, hier=hier)
        meta["fetch_meta"][name] = fmeta
        for k, v in farr.items():
            arrays[f"fetch_{name}_{k}"] = v
            specs[f"fetch_{name}_{k}"] = P(AXIS)

    if pg.layout == "csr":
        dbounds = device_edge_bounds(pg, D) if split else None
        if split:
            pb = dbounds["phys"]
            meta["M_phys"] = pg.M_phys
            meta["p_bounds"] = pb
            meta["P_loc"] = int(np.diff(pb).max())
            meta["device_edge_load"] = device_edge_loads(pg, D)
            arrays["phys_log"] = jnp.asarray(pg.phys_log, jnp.int32)
            specs["phys_log"] = P()
        base = np.arange(D) * m * n_loc        # a safe in-range pad id
        for name, off_name in (("eg", "eg_off"), ("all", "all_off")):
            off = (dbounds[name] if split
                   else csr_device_bounds(getattr(pg, off_name), M, D))
            src, vs = _pad_device_slices(
                np.asarray(getattr(pg, f"{name}_src")), off, base)
            dst, _ = _pad_device_slices(
                np.asarray(getattr(pg, f"{name}_dst")), off, np.zeros(D))
            w, _ = _pad_device_slices(
                np.asarray(getattr(pg, f"{name}_w")), off, np.zeros(D))
            arrays[f"{name}_src"] = src
            arrays[f"{name}_dst"] = dst
            arrays[f"{name}_w"] = w
            arrays[f"{name}_mask"] = vs
            specs.update({f"{name}_src": P(AXIS), f"{name}_dst": P(AXIS),
                          f"{name}_w": P(AXIS), f"{name}_mask": P(AXIS)})
            if split:
                pw, _ = _pad_device_slices(
                    np.asarray(getattr(pg, f"{name}_pw")), off, pb[:-1])
                arrays[f"{name}_pw"] = pw
                specs[f"{name}_pw"] = P(AXIS)
                # split device bounds cross worker state blocks: build the
                # static source-value fetch plan + compact per-edge index
                # (the padded src rows reuse base[d], a real slot, so pad
                # lanes simply share a fetched value and stay masked)
                need = [np.unique(src[d]) for d in range(D)]
                add_fetch(name, need)
                csrc = np.stack([
                    np.searchsorted(need[d], src[d]).astype(np.int32)
                    for d in range(D)])
                arrays[f"{name}_csrc"] = csrc
                specs[f"{name}_csrc"] = P(AXIS)
        off = (dbounds["mir"] if split
               else csr_device_bounds(pg.mir_eoff, M, D))
        esrc, vs = _pad_device_slices(np.asarray(pg.mir_esrc), off,
                                      np.zeros(D))
        edst, _ = _pad_device_slices(np.asarray(pg.mir_edst), off, base)
        ew, _ = _pad_device_slices(np.asarray(pg.mir_ew), off, np.zeros(D))
        arrays.update(mir_esrc=esrc, mir_edst=edst, mir_ew=ew, mir_emask=vs)
        specs.update(mir_esrc=P(AXIS), mir_edst=P(AXIS), mir_ew=P(AXIS),
                     mir_emask=P(AXIS))
        if split:
            pw, _ = _pad_device_slices(np.asarray(pg.mir_pw), off, pb[:-1])
            arrays["mir_pw"] = pw
            specs["mir_pw"] = P(AXIS)
    else:
        for name in ("eg_src", "eg_dst", "eg_mask", "eg_w",
                     "all_src", "all_dst", "all_mask", "all_w",
                     "mir_esrc", "mir_edst", "mir_emask", "mir_ew"):
            arrays[name] = getattr(pg, name)
            specs[name] = P(AXIS)

    # mirror-value fetch plan: each device needs the state slots of the
    # mirrored vertices referenced by ITS mirror edges (static)
    mir_ids_np = np.asarray(pg.mir_ids, np.int64)
    n_pad = M * n_loc
    esrc_np = np.asarray(arrays["mir_esrc"])
    emask_np = np.asarray(arrays["mir_emask"])
    if pg.layout != "csr":
        mm = M // D
        esrc_np = esrc_np.reshape(D, mm * esrc_np.shape[1])
        emask_np = emask_np.reshape(D, mm * emask_np.shape[1])
    need_lists, cesrc = [], []
    for d in range(D):
        safe = np.clip(esrc_np[d], 0, len(mir_ids_np) - 1)
        gids = mir_ids_np[safe]
        ok = emask_np[d] & (gids < n_pad)
        need = np.unique(gids[ok]) if ok.any() else np.zeros(0, np.int64)
        need_lists.append(need)
        pos = (np.searchsorted(need, gids) if len(need)
               else np.zeros(len(gids), np.int64))
        pos = np.where(ok, np.clip(pos, 0, max(len(need) - 1, 0)), 0)
        cesrc.append(pos.astype(np.int32))
    add_fetch("mir", need_lists)
    arrays["mir_cesrc"] = np.stack(cesrc)
    specs["mir_cesrc"] = P(AXIS)

    for kind in plan_kinds:
        with spans.span(spans.PLAN):
            pmeta, parrs = _sharded_plan(pg, D, hier, kind, chunks)
        meta["plan_meta"][kind] = pmeta
        for k, v in parrs.items():
            arrays[f"plan_{kind}_{k}"] = v
            specs[f"plan_{kind}_{k}"] = P(AXIS)
    if hier:
        # device-stacked leading axes shard over BOTH mesh axes (the
        # flat device order d = h*T + t IS the row-major (h, w) order)
        both = P((HAXIS, AXIS))
        specs = {k: (both if v == P(AXIS) else v)
                 for k, v in specs.items()}
    return meta, arrays, specs


# ---------------------------------------------------------------------------
# frozen shape profiles: resident executors that NEVER re-trace
# ---------------------------------------------------------------------------
#
# jax.jit caches on (function object, argument shapes/dtypes).  A resident
# program built by ``build_sharded`` keeps its function object alive, so
# the only way a graph mutation can force a re-trace is by changing the
# shapes of the ``arrays`` pytree — per-device edge caps, the mirror-id
# table length, the mirror fetch-plan tables — or a meta static like the
# pair_counts cap hint.  A ShardProfile freezes every one of those at
# warmup (with headroom), and ``reshard_arrays`` re-pads a folded graph's
# arrays to the exact same envelope: same function + same shapes = cache
# hit, zero re-traces, while an overflow past the envelope raises
# ``ProfileOverflow`` so the caller re-warms deliberately.  Padding is
# semantics-free by the masking contract (mask=False lanes contribute
# nothing to values or stats), and a frozen cap hint can only change how
# many overflow *rounds* a routed exchange takes — never its result.

class ProfileOverflow(ValueError):
    """The graph outgrew its frozen ShardProfile: re-warm the executor."""


@dataclasses.dataclass(frozen=True)
class ShardProfile:
    """Frozen shape envelope of a resident sharded executor (csr layout,
    1-D mesh, no split, no pallas plan tables)."""
    D: int
    eg_cap: int        # per-device Ch_msg edge rows
    all_cap: int       # per-device full-adjacency rows
    mir_cap: int       # per-device mirror fan-out rows
    n_mir: int         # replicated mirror-id table length
    fetch_cap: int     # mirror fetch plan per-device-pair lanes
    fetch_need: int    # mirror fetch plan compact buffer length
    cap_hint: Optional[int]  # frozen pair_counts routing cap


def _profile_supported(meta):
    if meta["layout"] != "csr":
        raise ValueError("ShardProfile needs layout='csr' (padded shapes "
                         "are already content-dependent per worker)")
    if meta["split"]:
        raise ValueError("ShardProfile does not support balance='split': "
                         "physical shard bounds are static meta, not "
                         "paddable arrays")
    if meta["hier"]:
        raise ValueError("ShardProfile supports the 1-D mesh only")
    if meta["plan_meta"]:
        raise ValueError("ShardProfile supports plan_kinds=() (dense "
                         "backend) only")


def shard_profile(pg, devices, slack: float = 1.25,
                  pad: int = 8) -> ShardProfile:
    """Measure ``pg``'s natural shard shapes and inflate them by
    ``slack`` (rounded up to ``pad`` lanes) into a frozen envelope with
    mutation headroom."""
    D, _ = _normalize_devices(devices)
    meta, arrays, _ = _shard_graph(pg, devices, ())
    _profile_supported(meta)

    def up(x):
        return int(-(-int(np.ceil(x * slack)) // pad) * pad)

    fm = meta["fetch_meta"]["mir"]
    hint = meta["cap_hint"]
    return ShardProfile(
        D=D,
        eg_cap=up(arrays["eg_src"].shape[1]),
        all_cap=up(arrays["all_src"].shape[1]),
        mir_cap=up(arrays["mir_esrc"].shape[1]),
        n_mir=up(arrays["mir_ids"].shape[0]),
        fetch_cap=up(fm["cap"]), fetch_need=up(fm["n_need"]),
        cap_hint=None if hint is None else up(hint))


def _pad_cols(a, cap, pad_col, what):
    """(D, c) -> (D, cap) padded with the per-device column ``pad_col``."""
    a = np.asarray(a)
    d, c = a.shape
    if c > cap:
        raise ProfileOverflow(f"{what}: {c} rows exceed the frozen "
                              f"profile cap {cap}")
    if c == cap:
        return a
    pad = np.broadcast_to(np.asarray(pad_col, a.dtype).reshape(d, 1),
                          (d, cap - c)).copy()
    return np.concatenate([a, pad], axis=1)


def _apply_profile(meta, arrays, prof: ShardProfile) -> None:
    """Re-pad freshly sharded ``arrays`` (and the content-dependent meta
    statics) to the frozen envelope, in place."""
    _profile_supported(meta)
    D, m, n_loc = meta["D"], meta["m_loc"], meta["n_loc"]
    if D != prof.D:
        raise ProfileOverflow(f"profile built for D={prof.D}, got D={D}")
    base = np.arange(D) * m * n_loc
    zero = np.zeros(D)
    for name, cap in (("eg", prof.eg_cap), ("all", prof.all_cap)):
        arrays[f"{name}_src"] = _pad_cols(arrays[f"{name}_src"], cap,
                                          base, f"{name}_src")
        arrays[f"{name}_dst"] = _pad_cols(arrays[f"{name}_dst"], cap,
                                          zero, f"{name}_dst")
        arrays[f"{name}_w"] = _pad_cols(arrays[f"{name}_w"], cap, zero,
                                        f"{name}_w")
        arrays[f"{name}_mask"] = _pad_cols(arrays[f"{name}_mask"], cap,
                                           zero, f"{name}_mask")
    arrays["mir_esrc"] = _pad_cols(arrays["mir_esrc"], prof.mir_cap,
                                   zero, "mir_esrc")
    arrays["mir_edst"] = _pad_cols(arrays["mir_edst"], prof.mir_cap,
                                   base, "mir_edst")
    arrays["mir_ew"] = _pad_cols(arrays["mir_ew"], prof.mir_cap, zero,
                                 "mir_ew")
    arrays["mir_emask"] = _pad_cols(arrays["mir_emask"], prof.mir_cap,
                                    zero, "mir_emask")
    arrays["mir_cesrc"] = _pad_cols(arrays["mir_cesrc"], prof.mir_cap,
                                    zero, "mir_cesrc")
    # replicated mirror tables: sentinel-padded ids (n_pad => inert in
    # every need-list and value gather), zero extra workers
    ids = np.asarray(arrays["mir_ids"])
    if len(ids) > prof.n_mir:
        raise ProfileOverflow(f"n_mir {len(ids)} exceeds the frozen "
                              f"profile {prof.n_mir}")
    sent = np.full(prof.n_mir - len(ids), meta["M"] * n_loc, ids.dtype)
    arrays["mir_ids"] = np.concatenate([ids, sent])
    nw = np.asarray(arrays["mir_nworkers"])
    arrays["mir_nworkers"] = np.concatenate(
        [nw, np.zeros(prof.n_mir - len(nw), nw.dtype)])
    # mirror fetch plan: -1 lanes are dropped by _fetch_planned; a larger
    # n_need only grows the compact buffer (real positions untouched)
    fm = meta["fetch_meta"]["mir"]
    if fm["cap"] > prof.fetch_cap or fm["n_need"] > prof.fetch_need:
        raise ProfileOverflow(
            f"mirror fetch plan (cap {fm['cap']}, n_need {fm['n_need']}) "
            f"exceeds the frozen profile (cap {prof.fetch_cap}, n_need "
            f"{prof.fetch_need})")
    for k in ("send_slot", "recv_pos"):
        a = np.asarray(arrays[f"fetch_mir_{k}"])
        out = np.full(a.shape[:2] + (prof.fetch_cap,), -1, a.dtype)
        out[:, :, :a.shape[2]] = a
        arrays[f"fetch_mir_{k}"] = out
    meta["fetch_meta"]["mir"] = {"cap": prof.fetch_cap,
                                 "n_need": prof.fetch_need}
    meta["cap_hint"] = prof.cap_hint


def reshard_arrays(pg, devices, profile: ShardProfile) -> Dict:
    """Arrays-only reshard of a (folded) graph under a frozen profile:
    feed the result to a program previously built with the SAME profile —
    shapes are envelope-stable, so the jit cache hits (zero re-trace)."""
    meta, arrays, _ = _shard_graph(pg, devices, ())
    _apply_profile(meta, arrays, profile)
    return arrays


# ---------------------------------------------------------------------------
# the inside-shard_map graph view
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardedGraph:
    """Device-local twin of PartitionedGraph inside the ``shard_map`` body.

    Duck-types the fields algorithms and channels read — ``M``/``n_loc``
    stay *global* (owner arithmetic, per-worker stats), edge/vertex arrays
    are the local shard, and the ``g*`` reductions become collectives.
    ``channels.broadcast`` & friends detect the ``axis`` attribute and
    route to the sharded implementations below."""
    M: int
    n_loc: int
    m_loc: int
    D: int
    n: int
    tau: int
    layout: str
    axis: object               # "w", or ("h", "w") on a 2-D mesh
    w0: jnp.ndarray            # global index of this device's first worker
    vmask: jnp.ndarray
    deg: jnp.ndarray
    eg_src: jnp.ndarray
    eg_dst: jnp.ndarray
    eg_mask: jnp.ndarray
    eg_w: jnp.ndarray
    all_src: jnp.ndarray
    all_dst: jnp.ndarray
    all_mask: jnp.ndarray
    all_w: jnp.ndarray
    mir_ids: jnp.ndarray
    mir_nworkers: jnp.ndarray
    mir_esrc: jnp.ndarray
    mir_edst: jnp.ndarray
    mir_emask: jnp.ndarray
    mir_ew: jnp.ndarray
    mir_cesrc: jnp.ndarray     # mirror edge -> index into the fetched values
    plans: Dict[str, TracedPlan] = dataclasses.field(default_factory=dict)
    fetch: Dict[str, TracedFetch] = dataclasses.field(default_factory=dict)
    cap_hint: Optional[int] = None
    # 2-D (host, device) mesh: T > 0 selects the hierarchical exchanges
    # (flat device d = h*T + t; intra-host axis "w" size T, host axis "h"
    # size H) with per-level cap hints replacing the flat one
    H: int = 1
    T: int = 0
    cap_hint_w: Optional[int] = None
    cap_hint_h: Optional[int] = None
    # double-buffered pipeline: chunk each routed exchange so chunk k's
    # all_to_all overlaps chunk k-1's local combine (results stay exact;
    # see _routed_scatter_combine / _combine_with_plan_sharded)
    pipeline: bool = False
    pipeline_chunks: int = 1
    # split partitions (physical shards as the device placement unit):
    split: bool = False
    M_phys: int = 0
    P_loc: int = 0                      # max shards per device
    p0: Optional[jnp.ndarray] = None    # first shard id of this device
    phys_log: Optional[jnp.ndarray] = None   # replicated (M_phys,)
    eg_pw: Optional[jnp.ndarray] = None      # device-local per-edge shards
    all_pw: Optional[jnp.ndarray] = None
    mir_pw: Optional[jnp.ndarray] = None
    eg_csrc: Optional[jnp.ndarray] = None    # edge -> fetched-source index
    all_csrc: Optional[jnp.ndarray] = None

    @property
    def n_pad(self) -> int:
        return self.M * self.n_loc

    @property
    def hier(self) -> bool:
        return self.T > 0

    def log_of(self, worker: jnp.ndarray) -> jnp.ndarray:
        """Physical shard ids -> logical worker ids (identity when the
        partition is not split)."""
        return self.phys_log[worker] if self.split else worker

    def local_ids(self) -> jnp.ndarray:
        return ((self.w0 + jnp.arange(self.m_loc))[:, None] * self.n_loc
                + jnp.arange(self.n_loc)[None, :])

    def worker_ids(self) -> jnp.ndarray:
        """(m_loc,) global worker indices of the local rows."""
        return self.w0 + jnp.arange(self.m_loc)

    def gany(self, x):
        return jax.lax.psum(jnp.any(x).astype(jnp.int32), self.axis) > 0

    def gall(self, x):
        return jax.lax.psum((~jnp.all(x)).astype(jnp.int32), self.axis) == 0

    def gsum(self, x):
        return jax.lax.psum(jnp.sum(x), self.axis)

    def gmax(self, x):
        return jax.lax.pmax(jnp.max(x), self.axis)

    def edge_src_values(self, state, src):
        if self.layout == "csr":
            if self.split:
                # split device bounds cross state blocks: read through the
                # static source fetch plan of the matching edge set
                if src is self.all_src:
                    fp, csrc = self.fetch["all"], self.all_csrc
                elif src is self.eg_src:
                    fp, csrc = self.fetch["eg"], self.eg_csrc
                else:
                    raise ValueError(
                        "split edge_src_values needs a planned edge set "
                        "(pass sg.all_src or sg.eg_src)")
                flat = state.reshape(-1)
                return _fetch_planned(self, fp, flat,
                                      jnp.zeros((), flat.dtype))[csrc]
            return state.reshape(-1)[src - self.w0 * self.n_loc]
        return state[jnp.arange(self.m_loc)[:, None], src]


def _make_sg(meta, a) -> ShardedGraph:
    layout = meta["layout"]
    m = meta["m_loc"]
    hier = meta.get("hier")
    if hier:
        H, T = hier
        axis = (HAXIS, AXIS)
    else:
        H, T = 1, 0
        axis = AXIS
    # on the 2-D mesh the tuple index IS the flat row-major device id
    # d = h*T + t, so all flat-id arithmetic (w0, owner checks) holds
    d = jax.lax.axis_index(axis).astype(jnp.int32)
    w0 = d * m

    def loc(name):
        # csr edge leaves arrive as (1, cap) device rows; padded rows as
        # (m, ...) shards
        x = a[name]
        if layout == "csr" and name.split("_")[0] in ("eg", "all", "mir") \
                and name not in ("mir_ids", "mir_nworkers"):
            return x[0]
        return x

    plans = {}
    for kind, pm in meta["plan_meta"].items():
        chunked = {}
        if "n_chunks" in pm:
            chunked = dict(
                n_chunks=pm["n_chunks"], ccap=pm["ccap"],
                cr=pm["cr"], cs=pm["cs"],
                crow=a[f"plan_{kind}_crow"][0],
                crow_ok=a[f"plan_{kind}_crow_ok"][0],
                crow_seg=a[f"plan_{kind}_crow_seg"][0],
                cxseg=a[f"plan_{kind}_cxseg"][0],
                cxval=a[f"plan_{kind}_cxval"][0],
                crblk=a[f"plan_{kind}_crblk"][0],
                crval=a[f"plan_{kind}_crval"][0])
        if "x1cap" in pm:
            chunked.update(
                x1cap=pm["x1cap"], n_iseg=pm["n_iseg"],
                x2cap=pm["x2cap"], hchunks=pm["hchunks"],
                x1seg=a[f"plan_{kind}_x1seg"][0],
                x1val=a[f"plan_{kind}_x1val"][0],
                iscat=a[f"plan_{kind}_iscat"][0],
                ival=a[f"plan_{kind}_ival"][0],
                x2seg=a[f"plan_{kind}_x2seg"][0],
                x2val=a[f"plan_{kind}_x2val"][0],
                r2blk=a[f"plan_{kind}_r2blk"][0],
                r2val=a[f"plan_{kind}_r2val"][0])
        plans[kind] = TracedPlan(
            nb=pm["nb"], eb=pm["eb"], B_per_w=pm["B_per_w"],
            n_blocks=pm["n_blocks"], n_rows=pm["n_rows"],
            n_segs=pm["n_segs"], xcap=pm["xcap"],
            row_gather=a[f"plan_{kind}_row_gather"][0],
            row_valid=a[f"plan_{kind}_row_valid"][0],
            row_local=a[f"plan_{kind}_row_local"][0],
            row_seg=a[f"plan_{kind}_row_seg"][0],
            seg_blk=a[f"plan_{kind}_seg_blk"][0],
            seg_worker=a[f"plan_{kind}_seg_worker"][0],
            xseg=a[f"plan_{kind}_xseg"][0],
            xval=a[f"plan_{kind}_xval"][0],
            rblk=a[f"plan_{kind}_rblk"][0],
            rval=a[f"plan_{kind}_rval"][0], **chunked)
    fetch = {}
    for name, fm in meta["fetch_meta"].items():
        if "n_gw" in fm:
            fetch[name] = TracedFetch(
                n_need=fm["n_need"], n_gw=fm["n_gw"],
                cap_a=fm["cap_a"], cap_b=fm["cap_b"],
                a_send=a[f"fetch_{name}_a_send"][0],
                a_recv=a[f"fetch_{name}_a_recv"][0],
                b_send=a[f"fetch_{name}_b_send"][0],
                b_recv=a[f"fetch_{name}_b_recv"][0])
        else:
            fetch[name] = TracedFetch(
                n_need=fm["n_need"], cap=fm["cap"],
                send_slot=a[f"fetch_{name}_send_slot"][0],
                recv_pos=a[f"fetch_{name}_recv_pos"][0])
    split = meta.get("split", False)
    extra = {}
    if split:
        extra = dict(
            split=True, M_phys=meta["M_phys"], P_loc=meta["P_loc"],
            p0=jnp.asarray(meta["p_bounds"][:-1], jnp.int32)[d],
            phys_log=a["phys_log"], eg_pw=loc("eg_pw"),
            all_pw=loc("all_pw"), mir_pw=loc("mir_pw"),
            eg_csrc=a["eg_csrc"][0], all_csrc=a["all_csrc"][0])
    return ShardedGraph(
        M=meta["M"], n_loc=meta["n_loc"], m_loc=m, D=meta["D"],
        n=meta["n"], tau=meta["tau"], layout=layout, axis=axis, w0=w0,
        H=H, T=T, cap_hint_w=meta.get("cap_hint_w"),
        cap_hint_h=meta.get("cap_hint_h"),
        vmask=a["vmask"], deg=a["deg"],
        eg_src=loc("eg_src"), eg_dst=loc("eg_dst"),
        eg_mask=loc("eg_mask"), eg_w=loc("eg_w"),
        all_src=loc("all_src"), all_dst=loc("all_dst"),
        all_mask=loc("all_mask"), all_w=loc("all_w"),
        mir_ids=a["mir_ids"], mir_nworkers=a["mir_nworkers"],
        mir_esrc=loc("mir_esrc"), mir_edst=loc("mir_edst"),
        mir_emask=loc("mir_emask"), mir_ew=loc("mir_ew"),
        mir_cesrc=a["mir_cesrc"][0],
        plans=plans, fetch=fetch, cap_hint=meta.get("cap_hint"),
        pipeline=meta.get("pipeline", False),
        pipeline_chunks=meta.get("pipeline_chunks", 1), **extra)


# ---------------------------------------------------------------------------
# routed exchange cores
# ---------------------------------------------------------------------------

def _place_rows(sg: ShardedGraph, local_counts: jnp.ndarray) -> jnp.ndarray:
    """(m_loc,) per-local-worker counts -> replicated (M,) via psum."""
    full = jnp.zeros((sg.M,), local_counts.dtype)
    full = jax.lax.dynamic_update_slice(full, local_counts, (sg.w0,))
    return jax.lax.psum(full, sg.axis)


def _scatter_workers(sg: ShardedGraph, workers, flags) -> jnp.ndarray:
    """Count ``flags`` at global ``workers`` -> replicated (M,)."""
    pw = jnp.zeros((sg.M,), jnp.int32).at[
        jnp.where(flags, workers, 0)].add(flags.astype(jnp.int32))
    return jax.lax.psum(pw, sg.axis)


def _bucket_by_device(sg: ShardedGraph, targets, valid):
    """Sort lanes by destination device (invalid last).  Returns
    (order, (D+1,) bucket offsets, per-pair round count)."""
    loc_n = sg.m_loc * sg.n_loc
    dd = jnp.where(valid,
                   jnp.clip(targets, 0, sg.n_pad - 1) // loc_n,
                   sg.D).astype(jnp.int32)
    order = jnp.argsort(dd, stable=True)
    off = jnp.searchsorted(dd[order], jnp.arange(sg.D + 1, dtype=jnp.int32))
    return order, off


def _rounds_for(sg: ShardedGraph, off: jnp.ndarray, cap: int) -> jnp.ndarray:
    """Replicated number of all_to_all rounds: the psum'd overflow signal.
    Balanced traffic fits the cap in one round; a hot destination just
    adds rounds (extra cap-sized exchanges), never dropped lanes."""
    counts = off[1:] - off[:-1]
    return jax.lax.pmax(((counts + cap - 1) // cap).max(), sg.axis)


def _round_lanes(off: jnp.ndarray, r, cap: int, L: int):
    """Round ``r``'s (D, cap) lane window into the device-sorted arrays:
    per destination device the slice [off[d] + r*cap, off[d+1]) clipped to
    ``cap`` lanes.  Returns (clipped indices, in-bucket validity) — the
    indexing core both routed exchanges share."""
    idx = off[:-1, None] + r * cap + jnp.arange(cap, dtype=jnp.int32)[None]
    ok = idx < off[1:, None]
    return jnp.clip(idx, 0, L - 1), ok


def _feat_elems(feat: tuple) -> int:
    e = 1
    for s in feat:
        e *= int(s)
    return e


def _pipeline_cap(sg: ShardedGraph, cap: int, feat_elems: int = 1) -> int:
    """Shrink a routed-exchange round cap so one join spans roughly
    ``sg.pipeline_chunks`` rounds — the chunks the double buffer overlaps.
    Only ever shrinks (an explicit small test cap passes through).

    For feature-blocked payloads the cap additionally shrinks by the
    payload width: the two in-flight slots hold ``cap x F`` elements, so
    sizing the chunk in *bytes* (lanes x F) keeps the pipeline's resident
    buffer flat as F grows.  Scalar payloads (``feat_elems == 1``) take
    the original expression unchanged — the F=1 chunking, and therefore
    the pipelined parity contract, is untouched."""
    if not (sg.pipeline and sg.pipeline_chunks > 1):
        return cap
    chunks = sg.pipeline_chunks * max(1, int(feat_elems))
    return min(cap, max(8, _pad8(-(-cap // chunks))))


@spans.scope(spans.EXCHANGE)
def _routed_scatter_combine(sg: ShardedGraph, targets, values, valid,
                            op: str, cap: Optional[int] = None
                            ) -> jnp.ndarray:
    """Destination-routed combine: (L,) lanes of (global target, value)
    pairs are bucketed by owner device, exchanged in cap-sized
    ``all_to_all`` rounds, and combined into MY local (m_loc*n_loc,)
    buffer — the per-device footprint is O(L + D*cap), never (n_pad,).

    ``sg.pipeline`` double-buffers the rounds: round r's all_to_all is
    issued before round r-1's received lanes scatter, so the collective
    flies while the combine runs.  Rounds still combine in the sequential
    order (r=0,1,...), so the result is bitwise identical."""
    if sg.hier:
        return _hier_scatter_combine(sg, targets, values, valid, op,
                                     cap=cap)
    D, loc_n = sg.D, sg.m_loc * sg.n_loc
    L = targets.shape[0]
    feat = planlib.feat_shape(values, 1)
    cap = _pipeline_cap(sg, cap or _cap_for(L, D), _feat_elems(feat))
    ident = identity_of(op, values.dtype)
    order, off = _bucket_by_device(sg, targets, valid)
    st_ = jnp.where(valid, targets, sg.n_pad)[order]
    sv_ = jnp.where(planlib.feat_mask(valid, values, 1), values,
                    ident)[order]
    rounds = _rounds_for(sg, off, cap)
    base = sg.w0 * sg.n_loc

    def _xchg(r):
        idxc, ok = _round_lanes(off, r, cap, L)
        t_send = jnp.where(ok, st_[idxc], sg.n_pad)
        sv_c = sv_[idxc]
        v_send = jnp.where(planlib.feat_mask(ok, sv_c, 2), sv_c, ident)
        return (jax.lax.all_to_all(t_send, sg.axis, 0, 0),
                jax.lax.all_to_all(v_send, sg.axis, 0, 0))

    def _combine(buf, recv):
        t_recv, v_recv = recv
        slot = t_recv - base
        okr = (slot >= 0) & (slot < loc_n)
        return scatter_op(op, buf, jnp.where(okr, slot, 0),
                          jnp.where(planlib.feat_mask(okr, v_recv, 2),
                                    v_recv, ident))

    buf0 = jnp.full((loc_n,) + feat, ident, values.dtype)
    if not sg.pipeline:
        return jax.lax.fori_loop(
            0, rounds, lambda r, buf: _combine(buf, _xchg(r)), buf0)

    def body(r, carry):
        buf, prev = carry
        cur = _xchg(r)                       # round r in flight...
        return _combine(buf, prev), cur      # ...while r-1 combines

    # prologue round 0; epilogue combines the last in-flight round.
    # rounds is replicated (pmax'd) so every device runs the same
    # collectives; rounds==0 leaves every lane masked -> buf0 unchanged.
    first = _xchg(jnp.zeros((), jnp.int32))
    buf, last = jax.lax.fori_loop(1, rounds, body, (buf0, first))
    return _combine(buf, last)


def _hier_caps(sg: ShardedGraph, L: int, cap,
               feat_elems: int = 1) -> Tuple[int, int]:
    """Per-level lane caps of one hierarchical routed exchange.  A flat
    int cap is a 1-D-mesh quantity (per-destination-*device*) and would
    silently under-cap the funnel legs here — the intra-host leg routes
    to T columns and the inter-host leg routes a whole column's residue
    to H hosts — so unless an explicit ``(cap1, cap2)`` pair is given,
    both caps are re-derived per level from the level-aware hints."""
    if isinstance(cap, tuple):
        cap1, cap2 = int(cap[0]), int(cap[1])
    else:
        cap1 = _cap_for(L, sg.T, sg.cap_hint_w)
        cap2 = _cap_for(sg.T * cap1, sg.H, sg.cap_hint_h)
    # the pipeline chunks the INTER-host leg (where the overlap pays)
    return cap1, _pipeline_cap(sg, cap2, feat_elems)


def _bucket_level(sg: ShardedGraph, targets, valid, level: str):
    """Sort lanes by the ``level`` coordinate of the destination device
    (column within host for ``"w"``, host for ``"h"``; invalid last).
    Returns (order, (K+1,) bucket offsets) with K the axis size."""
    loc_n = sg.m_loc * sg.n_loc
    dd = jnp.clip(targets, 0, sg.n_pad - 1) // loc_n
    K = sg.T if level == "w" else sg.H
    coord = dd % sg.T if level == "w" else dd // sg.T
    key = jnp.where(valid, coord, K).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    off = jnp.searchsorted(key[order], jnp.arange(K + 1, dtype=jnp.int32))
    return order, off


@spans.scope(spans.EXCHANGE)
def _hier_scatter_combine(sg: ShardedGraph, targets, values, valid,
                          op: str, cap=None) -> jnp.ndarray:
    """2-D twin of :func:`_routed_scatter_combine`: lanes first route to
    the destination *column* within my host (axis ``"w"`` rounds), the
    column device segment-combines everything it received by target —
    the per-level Theorem-1 combine — and only the combined residue
    crosses the host axis (``"h"`` rounds) to the owner, which combines
    into its local buffer.  Round counts are pmax'd over the whole mesh
    so every device runs the same collectives; with ``sg.pipeline`` the
    inter-host rounds are double-buffered (round r's all_to_all flies
    while round r-1 scatters — the leg where the overlap win lives)."""
    H, T = sg.H, sg.T
    loc_n = sg.m_loc * sg.n_loc
    n_pad = sg.n_pad
    L = targets.shape[0]
    feat = planlib.feat_shape(values, 1)
    cap1, cap2 = _hier_caps(sg, L, cap, _feat_elems(feat))
    ident = identity_of(op, values.dtype)
    order, off = _bucket_level(sg, targets, valid, "w")
    st_ = jnp.where(valid, targets, n_pad)[order]
    sv_ = jnp.where(planlib.feat_mask(valid, values, 1), values,
                    ident)[order]
    rounds1 = _rounds_for(sg, off, cap1)
    base = sg.w0 * sg.n_loc
    L2 = T * cap1
    zerow = jnp.zeros((L2,), jnp.int32)

    def inner(buf, tf, vf):
        # intermediate combine: duplicates aimed at the same target merge
        # BEFORE crossing the host axis (worker key 0 -> key by target)
        realf, seg_t, seg_val, _, _ = planlib.sorted_segments_flat(
            tf, vf, tf < n_pad, zerow, op, n_pad)
        ord2, off2 = _bucket_level(sg, seg_t, realf, "h")
        t2_ = jnp.where(realf, seg_t, n_pad)[ord2]
        v2_ = jnp.where(planlib.feat_mask(realf, seg_val, 1), seg_val,
                        ident)[ord2]
        rounds2 = _rounds_for(sg, off2, cap2)

        def _xchg(r):
            idxc, ok = _round_lanes(off2, r, cap2, L2)
            t_send = jnp.where(ok, t2_[idxc], n_pad)
            v2_c = v2_[idxc]
            v_send = jnp.where(planlib.feat_mask(ok, v2_c, 2), v2_c,
                               ident)
            return (jax.lax.all_to_all(t_send, HAXIS, 0, 0),
                    jax.lax.all_to_all(v_send, HAXIS, 0, 0))

        def _combine(b, recv):
            t_recv, v_recv = recv
            slot = t_recv - base
            okr = (slot >= 0) & (slot < loc_n)
            return scatter_op(op, b, jnp.where(okr, slot, 0),
                              jnp.where(planlib.feat_mask(okr, v_recv, 2),
                                        v_recv, ident))

        if not sg.pipeline:
            return jax.lax.fori_loop(
                0, rounds2, lambda r, b: _combine(b, _xchg(r)), buf)

        def body(r, carry):
            b, prev = carry
            cur = _xchg(r)                   # round r in flight...
            return _combine(b, prev), cur    # ...while r-1 combines

        first = _xchg(jnp.zeros((), jnp.int32))
        buf, last = jax.lax.fori_loop(1, rounds2, body, (buf, first))
        return _combine(buf, last)

    def outer(r, buf):
        idxc, ok = _round_lanes(off, r, cap1, L)
        t_send = jnp.where(ok, st_[idxc], n_pad)       # (T, cap1)
        sv_c = sv_[idxc]
        v_send = jnp.where(planlib.feat_mask(ok, sv_c, 2), sv_c, ident)
        t_r = jax.lax.all_to_all(t_send, AXIS, 0, 0)
        v_r = jax.lax.all_to_all(v_send, AXIS, 0, 0)
        return inner(buf, t_r.reshape(-1), v_r.reshape((-1,) + feat))

    buf0 = jnp.full((loc_n,) + feat, ident, values.dtype)
    return jax.lax.fori_loop(0, rounds1, outer, buf0)


@spans.scope(spans.EXCHANGE)
def _routed_fetch(sg: ShardedGraph, vals, targets, valid,
                  cap: Optional[int] = None) -> jnp.ndarray:
    """The request-respond transport: a real two-round trip.  (L,) global
    ``targets`` are bucketed by owner device; requests travel out in
    cap-sized ``all_to_all`` rounds, owners answer from their local
    (m_loc, n_loc) shard, responses travel back on the mirrored lanes.
    Returns (L,) gathered values, 0 where ``~valid`` (the reference
    convention for masked request lanes).

    ``sg.pipeline`` double-buffers the request rounds: request-chunk r is
    in flight (out and back) while request-chunk r-1's responses write
    into the output.  Rounds write disjoint lanes, so the result is
    bitwise identical to the sequential loop."""
    if sg.hier:
        return _hier_routed_fetch(sg, vals, targets, valid, cap=cap)
    D, loc_n = sg.D, sg.m_loc * sg.n_loc
    L = targets.shape[0]
    feat = planlib.feat_shape(vals, 2)
    cap = _pipeline_cap(sg, cap or _cap_for(L, D), _feat_elems(feat))
    flat = vals.reshape((-1,) + feat)
    zero = jnp.zeros((), vals.dtype)
    ok_t = valid & (targets >= 0) & (targets < sg.n_pad)
    order, off = _bucket_by_device(sg, targets, ok_t)
    st_ = jnp.where(ok_t, targets, sg.n_pad)[order]
    rounds = _rounds_for(sg, off, cap)
    base = sg.w0 * sg.n_loc

    def _trip(r):
        idxc, ok = _round_lanes(off, r, cap, L)
        req = jnp.where(ok, st_[idxc], sg.n_pad)
        req_r = jax.lax.all_to_all(req, sg.axis, 0, 0)
        slot = req_r - base
        okr = (slot >= 0) & (slot < loc_n)
        got_r = flat[jnp.clip(slot, 0, loc_n - 1)]
        resp = jnp.where(planlib.feat_mask(okr, got_r, 2), got_r, zero)
        return idxc, ok, jax.lax.all_to_all(resp, sg.axis, 0, 0)

    def _write(out, trip):
        idxc, ok, resp_b = trip
        return out.at[jnp.where(ok, idxc, L)].set(
            jnp.where(planlib.feat_mask(ok, resp_b, 2), resp_b, zero))

    out0 = jnp.zeros((L + 1,) + feat, vals.dtype)
    if not sg.pipeline:
        got_sorted = jax.lax.fori_loop(
            0, rounds, lambda r, out: _write(out, _trip(r)), out0)[:L]
    else:
        def body(r, carry):
            out, prev = carry
            cur = _trip(r)
            return _write(out, prev), cur

        first = _trip(jnp.zeros((), jnp.int32))
        out, last = jax.lax.fori_loop(1, rounds, body, (out0, first))
        got_sorted = _write(out, last)[:L]
    got = jnp.zeros((L,) + feat, vals.dtype).at[order].set(got_sorted)
    return jnp.where(planlib.feat_mask(ok_t, got, 1), got, zero)


@spans.scope(spans.EXCHANGE)
def _hier_routed_fetch(sg: ShardedGraph, vals, targets, valid,
                       cap=None) -> jnp.ndarray:
    """2-D twin of :func:`_routed_fetch`: requests first route to the
    owner's *column* within my host (axis ``"w"`` rounds); the column
    device sorts the host's requests and deduplicates them — only one
    head request per distinct target crosses the host axis (Theorem 3
    applied per level) — the owner answers over the ``"h"`` trip, the
    response is propagated back down the duplicate segments, unsorted,
    and returned over the mirrored ``"w"`` lanes.  With ``sg.pipeline``
    the inter-host trips are double-buffered."""
    H, T = sg.H, sg.T
    loc_n = sg.m_loc * sg.n_loc
    n_pad = sg.n_pad
    L = targets.shape[0]
    feat = planlib.feat_shape(vals, 2)
    cap1, cap2 = _hier_caps(sg, L, cap, _feat_elems(feat))
    flat = vals.reshape((-1,) + feat)
    zero = jnp.zeros((), vals.dtype)
    ok_t = valid & (targets >= 0) & (targets < n_pad)
    order, off = _bucket_level(sg, targets, ok_t, "w")
    st_ = jnp.where(ok_t, targets, n_pad)[order]
    rounds1 = _rounds_for(sg, off, cap1)
    base = sg.w0 * sg.n_loc
    Lr = T * cap1

    def gateway(reqs):
        # host-level dedup: sort the host's requests, fetch one head per
        # distinct target over the host axis, fan the response back down
        ord2 = jnp.argsort(reqs, stable=True)
        rs = reqs[ord2]
        first = (rs < n_pad) & jnp.concatenate(
            [jnp.ones((1,), bool), rs[1:] != rs[:-1]])
        ord3, off2 = _bucket_level(sg, rs, first, "h")
        rh_ = jnp.where(first, rs, n_pad)[ord3]
        rounds2 = _rounds_for(sg, off2, cap2)

        def _trip(r):
            idxc, ok = _round_lanes(off2, r, cap2, Lr)
            req = jnp.where(ok, rh_[idxc], n_pad)
            req_r = jax.lax.all_to_all(req, HAXIS, 0, 0)
            slot = req_r - base
            okr = (slot >= 0) & (slot < loc_n)
            got_r = flat[jnp.clip(slot, 0, loc_n - 1)]
            resp = jnp.where(planlib.feat_mask(okr, got_r, 2), got_r,
                             zero)
            return idxc, ok, jax.lax.all_to_all(resp, HAXIS, 0, 0)

        def _write(out, trip):
            idxc, ok, resp_b = trip
            return out.at[jnp.where(ok, idxc, Lr)].set(
                jnp.where(planlib.feat_mask(ok, resp_b, 2), resp_b, zero))

        out0 = jnp.zeros((Lr + 1,) + feat, vals.dtype)
        if not sg.pipeline:
            head3 = jax.lax.fori_loop(
                0, rounds2, lambda r, o: _write(o, _trip(r)), out0)[:Lr]
        else:
            def body(r, carry):
                o, prev = carry
                cur = _trip(r)
                return _write(o, prev), cur

            ft = _trip(jnp.zeros((), jnp.int32))
            out, last = jax.lax.fori_loop(1, rounds2, body, (out0, ft))
            head3 = _write(out, last)[:Lr]
        heads = jnp.zeros((Lr,) + feat, vals.dtype).at[ord3].set(head3)
        hidx = jax.lax.cummax(
            jnp.where(first, jnp.arange(Lr, dtype=jnp.int32), 0))
        got = jnp.zeros((Lr,) + feat, vals.dtype).at[ord2].set(heads[hidx])
        return jnp.where(planlib.feat_mask(reqs < n_pad, got, 1), got,
                         zero)

    def outer(r, out):
        idxc, ok = _round_lanes(off, r, cap1, L)
        req = jnp.where(ok, st_[idxc], n_pad)          # (T, cap1)
        req_r = jax.lax.all_to_all(req, AXIS, 0, 0)
        got_r = gateway(req_r.reshape(-1)).reshape((T, cap1) + feat)
        resp_b = jax.lax.all_to_all(got_r, AXIS, 0, 0)
        return out.at[jnp.where(ok, idxc, L)].set(
            jnp.where(planlib.feat_mask(ok, resp_b, 2), resp_b, zero))

    out0 = jnp.zeros((L + 1,) + feat, vals.dtype)
    got_sorted = jax.lax.fori_loop(0, rounds1, outer, out0)[:L]
    got = jnp.zeros((L,) + feat, vals.dtype).at[order].set(got_sorted)
    return jnp.where(planlib.feat_mask(ok_t, got, 1), got, zero)


# ---------------------------------------------------------------------------
# sharded channel implementations
# ---------------------------------------------------------------------------

@spans.scope(spans.EXCHANGE)
def _plan_exchange_pipelined(sg: ShardedGraph, plan: TracedPlan,
                             flat_vals: jnp.ndarray, op: str,
                             loc: jnp.ndarray, ident) -> jnp.ndarray:
    """The chunked plan exchange (see _combine_with_plan_sharded): a
    Python-unrolled double buffer over the static ``plan.n_chunks``
    chunks.  Chunk c's row subset runs the block-combine kernel and its
    segment partials are put on the wire before chunk c-1's received
    partials scatter locally."""

    feat = planlib.feat_shape(flat_vals, 1)

    def send(c):
        rows_ok = plan.crow_ok[c]
        with spans.scope(spans.COMBINE):
            row_out = planlib.combine_rows_subset(
                plan, flat_vals, plan.crow[c], rows_ok, op)
            sbuf = jnp.full((plan.cs, plan.nb) + feat, ident,
                            flat_vals.dtype)
            seg_out = scatter_op(
                op, sbuf, jnp.where(rows_ok, plan.crow_seg[c], 0),
                jnp.where(planlib.feat_mask(rows_ok[:, None], row_out, 2),
                          row_out, ident))
        g = seg_out[plan.cxseg[c]]
        snd = jnp.where(planlib.feat_mask(plan.cxval[c][:, :, None], g, 3),
                        g, ident)
        return jax.lax.all_to_all(snd, sg.axis, 0, 0)

    @spans.scope(spans.COMBINE)
    def combine(buf, c, recv):
        return scatter_op(
            op, buf, jnp.where(plan.crval[c], plan.crblk[c], 0),
            jnp.where(planlib.feat_mask(plan.crval[c][:, :, None], recv, 3),
                      recv, ident))

    recv = send(0)
    for c in range(1, plan.n_chunks):
        nxt = send(c)                        # chunk c in flight...
        loc = combine(loc, c - 1, recv)      # ...while c-1 scatters
        recv = nxt
    return combine(loc, plan.n_chunks - 1, recv)


@spans.scope(spans.EXCHANGE)
def _plan_exchange_hier(sg: ShardedGraph, plan: TracedPlan,
                        seg_out: jnp.ndarray, op: str,
                        loc: jnp.ndarray, ident) -> jnp.ndarray:
    """The two-leg static plan exchange (see :func:`_hier_plan_tables`):
    my segment partials ride ONE intra-host all_to_all to the device of
    their destination column, the column device op-combines everything
    it received by global destination block (``n_iseg`` compact
    intermediate segments — never an O(n) buffer), and only the combined
    residue crosses the host axis.  With the pipeline on, the inter-host
    leg is blocked into ``plan.hchunks`` static position-chunks so chunk
    c's all_to_all flies while chunk c-1's received residue scatters."""
    feat = planlib.feat_shape(seg_out, 2)
    # leg 1 (intra-host): my segments to their destination column
    g1 = seg_out[plan.x1seg]
    send1 = jnp.where(planlib.feat_mask(plan.x1val[:, :, None], g1, 3),
                      g1, ident)
    recv1 = jax.lax.all_to_all(send1, AXIS, 0, 0)      # (T, x1cap, nb)
    # intermediate combine by destination block (per-level Theorem 1)
    ibuf = jnp.full((plan.n_iseg, plan.nb) + feat, ident, seg_out.dtype)
    ibuf = scatter_op(
        op, ibuf, jnp.where(plan.ival, plan.iscat, 0),
        jnp.where(planlib.feat_mask(plan.ival[:, :, None], recv1, 3),
                  recv1, ident))

    # leg 2 (inter-host): only the combined residue crosses hosts
    def send2(sl):
        g2 = ibuf[plan.x2seg[:, sl]]
        snd = jnp.where(planlib.feat_mask(plan.x2val[:, sl, None], g2, 3),
                        g2, ident)
        return jax.lax.all_to_all(snd, HAXIS, 0, 0)

    def combine2(buf, sl, recv):
        return scatter_op(
            op, buf, jnp.where(plan.r2val[:, sl], plan.r2blk[:, sl], 0),
            jnp.where(planlib.feat_mask(plan.r2val[:, sl, None], recv, 3),
                      recv, ident))

    C = plan.hchunks if sg.pipeline else 1
    ck = -(-plan.x2cap // C)
    sls = [slice(c * ck, min((c + 1) * ck, plan.x2cap)) for c in range(C)]
    recv = send2(sls[0])
    for c in range(1, C):
        nxt = send2(sls[c])                  # chunk c in flight...
        loc = combine2(loc, sls[c - 1], recv)   # ...while c-1 scatters
        recv = nxt
    return combine2(loc, sls[-1], recv)


def _combine_with_plan_sharded(sg: ShardedGraph, plan: TracedPlan,
                               flat_vals: jnp.ndarray, op: str,
                               flat_hits: Optional[jnp.ndarray] = None,
                               count_cross: bool = True,
                               exchange: bool = True):
    """Per-device destination-blocked combine + destination-routed
    segment exchange: my (source, block) segment partials travel straight
    to the device owning their block through ONE statically-capped
    ``all_to_all``; I scatter the segments routed to me into my local
    (m_loc*B_per_w, nb) block range.  Never a global (n_blocks, nb)
    buffer, never an all-reduce over one.

    ``exchange=False`` skips the collective when the caller knows every
    segment is destination-local (the non-split mirror fan-out: mirror
    edges are destination-sharded, so self-routing them through the
    all_to_all would be a pointless per-superstep collective).

    When ``sg.pipeline`` and the plan carries chunk tables, the exchange
    is blocked into ``plan.n_chunks`` position-chunks of the xcap axis:
    chunk c's rows combine and its all_to_all is issued while chunk c-1's
    received segments scatter into ``loc`` — the double-buffered overlap.
    Rows are independent in the block-combine kernel and every real
    segment lands in exactly one chunk, so min/max/int results stay
    bitwise identical (float-sum scatter order changes within the
    tolerance the parity harness already grants sum combines)."""
    ident = identity_of(op, flat_vals.dtype)
    feat = planlib.feat_shape(flat_vals, 1)
    nbl = sg.m_loc * plan.B_per_w
    loc = jnp.full((nbl, plan.nb) + feat, ident, flat_vals.dtype)
    if exchange and sg.pipeline and plan.crow is not None \
            and plan.n_chunks > 1:
        loc = _plan_exchange_pipelined(sg, plan, flat_vals, op, loc, ident)
    else:
        gathered = flat_vals[plan.row_gather]
        packed = jnp.where(planlib.feat_mask(plan.row_valid, gathered, 2),
                           gathered, ident)
        row_out = planlib._combine_rows(packed, plan.row_local, op, plan.nb)
        seg_buf = jnp.full((plan.n_segs, plan.nb) + feat, ident,
                           flat_vals.dtype)
        seg_out = scatter_op(op, seg_buf, plan.row_seg, row_out)
        if exchange:
            if plan.x1seg is not None:
                loc = _plan_exchange_hier(sg, plan, seg_out, op, loc,
                                          ident)
            else:
                with spans.scope(spans.EXCHANGE):
                    g = seg_out[plan.xseg]
                    send = jnp.where(
                        planlib.feat_mask(plan.xval[:, :, None], g, 3),
                        g, ident)
                    recv = jax.lax.all_to_all(send, sg.axis, 0, 0)
                loc = scatter_op(
                    op, loc, jnp.where(plan.rval, plan.rblk, 0),
                    jnp.where(
                        planlib.feat_mask(plan.rval[:, :, None], recv, 3),
                        recv, ident))
        else:
            # all segments are mine: scatter by local block id directly
            # (padded dummy segments carry all-identity rows — harmless)
            lblk = jnp.clip(plan.seg_blk - sg.w0 * plan.B_per_w, 0, nbl - 1)
            loc = scatter_op(op, loc, lblk, seg_out)
    inbox = loc.reshape((sg.m_loc, plan.B_per_w * plan.nb) + feat
                        )[:, :sg.n_loc]

    stats = None
    if count_cross:
        # mask-driven accounting (TracedPlan duck-types EdgePlan here)
        with spans.scope(spans.STATS):
            sh = planlib.plan_seg_hits(plan, flat_hits)
            seg_log = sg.log_of(plan.seg_worker)
            owner = plan.seg_blk // plan.B_per_w
            cross = sh & (owner != seg_log)[:, None]
            msgs = jax.lax.psum(cross.sum().astype(jnp.int32), sg.axis)
            per_worker = jnp.zeros((sg.M,), jnp.int32).at[seg_log].add(
                cross.sum(axis=1).astype(jnp.int32))
            stats = (msgs, jax.lax.psum(per_worker, sg.axis))
    return inbox, stats


def _combine_sorted_rows_sharded(sg: ShardedGraph, targets, values, mask,
                                 op: str):
    """Sharded twin of plan.combine_sorted: the shared segment core
    (``plan.sorted_segments``) runs on the local (m_loc, K) rows, then the
    surviving segments are destination-routed (all_to_all rounds) into the
    owners' local buffers; source rows are rebased by ``w0`` for the
    accounting.  Crossness is mask-driven: a live segment IS >= 1 real
    message, whatever its combined payload."""
    n_pad = sg.n_pad
    real, seg_t, seg_val, seg_row, ident = planlib.sorted_segments(
        targets, values, mask, op, n_pad)

    buf = _routed_scatter_combine(sg, seg_t, seg_val, real, op)
    inbox = buf.reshape((sg.m_loc, sg.n_loc)
                        + planlib.feat_shape(values, 2))

    with spans.scope(spans.STATS):
        cross = real & (seg_t // sg.n_loc != seg_row + sg.w0)
        msgs = jax.lax.psum(cross.sum().astype(jnp.int32), sg.axis)
        per_worker = _scatter_workers(sg, seg_row + sg.w0, cross)
    return inbox, (msgs, per_worker)


def _combine_sorted_flat_sharded(sg: ShardedGraph, targets, values, mask,
                                 worker, op: str,
                                 cap: Optional[int] = None):
    """Flat-csr twin: ``plan.sorted_segments_flat`` on the local (E_dev,)
    edges (source workers already global — physical shard ids under a
    split partition), destination-routed exchange, mask-driven counts."""
    n_pad = sg.n_pad
    real, seg_t, seg_val, seg_w, ident = planlib.sorted_segments_flat(
        targets, values, mask, worker, op, n_pad)

    buf = _routed_scatter_combine(sg, seg_t, seg_val, real, op, cap=cap)
    inbox = buf.reshape((sg.m_loc, sg.n_loc)
                        + planlib.feat_shape(values, 1))

    with spans.scope(spans.STATS):
        seg_log = sg.log_of(jnp.where(real, seg_w, 0))
        cross = real & (seg_t // sg.n_loc != seg_log)
        msgs = jax.lax.psum(cross.sum().astype(jnp.int32), sg.axis)
        per_worker = _scatter_workers(sg, seg_log, cross)
    return inbox, (msgs, per_worker)


def push_combined_sharded(sg: ShardedGraph, targets, values, mask, op: str,
                          backend: str = "dense",
                          plan: Optional[TracedPlan] = None):
    """Sharded Ch_msg, padded rows: local (m_loc, K) edges.  With a plan
    the combine runs destination-blocked through the kernel path; without
    one (dense backend, runtime targets) through the sorted segmented
    core.  Both exchange destination-routed — inboxes and stats are
    identical to the reference paths (min/max bitwise, stats exact)."""
    with spans.scope(spans.STATS):
        gw = sg.worker_ids()[:, None]
        raw_cross = mask & ((targets // sg.n_loc) != gw)
        base = {"msgs_basic": jax.lax.psum(raw_cross.sum(), sg.axis),
                "per_worker_basic": _place_rows(sg, raw_cross.sum(axis=1))}

    if backend == "pallas" and plan is not None:
        ident = identity_of(op, values.dtype)
        masked = jnp.where(planlib.feat_mask(mask, values, 2), values,
                           ident)
        inbox, (msgs, pw) = _combine_with_plan_sharded(
            sg, plan, masked.reshape((-1,) + planlib.feat_shape(values, 2)),
            op, flat_hits=mask.reshape(-1))
    else:
        inbox, (msgs, pw) = _combine_sorted_rows_sharded(
            sg, targets, values, mask, op)
    stats = {"msgs_combined": msgs, "per_worker_combined": pw}
    stats.update(base)
    return inbox, stats


def push_combined_flat_sharded(sg: ShardedGraph, targets, values, mask,
                               worker, op: str, backend: str = "dense",
                               plan: Optional[TracedPlan] = None):
    """Sharded Ch_msg, csr layout: local flat (E_dev,) edges with global
    per-edge source workers (physical shard ids under a split partition —
    a shard never straddles devices, so the per-device distinct-pair
    accounting composes exactly across any device count)."""
    with spans.scope(spans.STATS):
        wlog = sg.log_of(worker)
        raw_cross = mask & ((targets // sg.n_loc) != wlog)
        base = {"msgs_basic": jax.lax.psum(raw_cross.sum(), sg.axis),
                "per_worker_basic": _scatter_workers(sg, wlog, raw_cross)}

    if backend == "pallas" and plan is not None:
        ident = identity_of(op, values.dtype)
        masked = jnp.where(planlib.feat_mask(mask, values, 1), values,
                           ident)
        inbox, (msgs, pw) = _combine_with_plan_sharded(
            sg, plan, masked, op, flat_hits=mask)
    else:
        inbox, (msgs, pw) = _combine_sorted_flat_sharded(
            sg, targets, values, mask, worker, op,
            cap=(_cap_for(targets.shape[0], sg.D, sg.cap_hint)
                 if sg.cap_hint else None))
    stats = {"msgs_combined": msgs, "per_worker_combined": pw}
    stats.update(base)
    return inbox, stats


def push_mirror_sharded(sg: ShardedGraph, vals, active, op: str,
                        relay: str = "none", backend: str = "dense"):
    """Sharded Ch_mir: each device fetches the mirror values it actually
    references through the static mirror fetch plan (owner devices serve
    their active mirrored vertices; ONE statically-capped all_to_all —
    never an all-reduce over the full mirror set), then fans out on the
    local mirror edges.  Stats are owner-side and psum-merged: a mirrored
    vertex is owned by exactly one device, so the counts compose
    exactly."""
    ident = identity_of(op, vals.dtype)
    n_pad = sg.n_pad
    loc_n = sg.m_loc * sg.n_loc
    feat = planlib.feat_shape(vals, 2)
    flat_vals = vals.reshape((-1,) + feat)
    flat_act = active.reshape(-1)
    contrib = jnp.where(planlib.feat_mask(flat_act, flat_vals, 1),
                        flat_vals, ident)               # owner-side payload
    lv = _fetch_planned(sg, sg.fetch["mir"], contrib, ident)

    cesrc = (sg.mir_cesrc if sg.layout == "csr"
             else sg.mir_cesrc.reshape(sg.mir_esrc.shape))
    raw = lv[cesrc]
    ev = relay_values(raw, sg.mir_ew, relay, cesrc.ndim)
    if feat:
        # feature payloads can legitimately equal the identity, so edge
        # activity is fetched explicitly instead of read off the values
        la = _fetch_planned(sg, sg.fetch["mir"],
                            flat_act.astype(jnp.int32),
                            jnp.zeros((), jnp.int32))
        act_e = sg.mir_emask & (la[cesrc] > 0)
        ev = jnp.where(act_e[..., None], ev, ident)
    else:
        act_e = sg.mir_emask & (raw != ident)
        ev = jnp.where(act_e, ev, ident)
    if backend == "pallas":
        # a non-split partition's mirror edges are destination-sharded:
        # every plan segment is local, so the exchange is skipped
        inbox, _ = _combine_with_plan_sharded(
            sg, sg.plans["mir"], ev.reshape((-1,) + feat), op,
            count_cross=False, exchange=sg.split)
    elif sg.layout == "csr":
        if sg.split:
            # shard placement can put fan-out edges on a device that does
            # not own their destination rows: route the combined values
            buf = _routed_scatter_combine(sg, sg.mir_edst, ev, act_e, op)
            inbox = buf.reshape((sg.m_loc, sg.n_loc) + feat)
        else:
            buf = jnp.full((loc_n,) + feat, ident, vals.dtype)
            inbox = scatter_op(op, buf, sg.mir_edst - sg.w0 * sg.n_loc,
                               ev).reshape((sg.m_loc, sg.n_loc) + feat)
    else:
        def fan_out(edst, emask, ev_row):
            buf = jnp.full((sg.n_loc,) + feat, ident, vals.dtype)
            return scatter_op(op, buf, jnp.where(emask, edst, 0), ev_row)

        inbox = jax.vmap(fan_out)(sg.mir_edst, sg.mir_emask, ev)

    # owner-side mask-driven stats: an ACTIVE mirrored vertex is broadcast
    # to its hosting workers whatever its value; each device charges the
    # mirrored vertices it owns and the psum restores the exact totals
    with spans.scope(spans.STATS):
        safe_g = jnp.clip(sg.mir_ids, 0, n_pad - 1)
        valid = sg.mir_ids < n_pad
        slot = safe_g - sg.w0 * sg.n_loc
        owned = (slot >= 0) & (slot < loc_n)
        act = flat_act[jnp.clip(slot, 0, loc_n - 1)]
        sent = jnp.where(valid & owned & act, sg.mir_nworkers, 0)
        msgs = jax.lax.psum(sent.sum(), sg.axis)
        owner_w = jnp.clip(safe_g // sg.n_loc, 0, sg.M - 1)
        per_worker = jnp.zeros((sg.M,), sent.dtype).at[owner_w].add(sent)
        per_worker = jax.lax.psum(per_worker, sg.axis)
    return inbox, {"msgs_mirror": msgs, "per_worker_mirror": per_worker}


@spans.scope(spans.COMBINE)
def broadcast_sharded(sg: ShardedGraph, vals, active, op: str,
                      relay: str = "none", use_mirroring: bool = True,
                      backend: str = "dense"):
    """Sharded twin of channels.broadcast (identical stats keys/values)."""
    esrc = sg.eg_src if use_mirroring else sg.all_src
    edst = sg.eg_dst if use_mirroring else sg.all_dst
    emask = sg.eg_mask if use_mirroring else sg.all_mask
    ew = sg.eg_w if use_mirroring else sg.all_w
    plan = (sg.plans.get("eg" if use_mirroring else "all")
            if backend == "pallas" else None)
    feat = planlib.feat_shape(vals, 2)
    if sg.layout == "csr":
        if sg.split:
            # edge-balanced device bounds: sources can be remote workers —
            # read them through the static source fetch plan (owner
            # devices serve exactly the slots this device's edges need)
            kind = "eg" if use_mirroring else "all"
            fp = sg.fetch[kind]
            csrc = sg.eg_csrc if use_mirroring else sg.all_csrc
            cv = _fetch_planned(sg, fp, vals.reshape((-1,) + feat),
                                jnp.zeros((), vals.dtype))
            ca = _fetch_planned(sg, fp,
                                active.reshape(-1).astype(jnp.int32),
                                jnp.zeros((), jnp.int32))
            src_val, src_act = cv[csrc], ca[csrc] > 0
            worker = sg.eg_pw if use_mirroring else sg.all_pw
        else:
            loc_src = esrc - sg.w0 * sg.n_loc
            src_val = vals.reshape((-1,) + feat)[loc_src]
            src_act = active.reshape(-1)[loc_src]
            worker = esrc // sg.n_loc
        v = relay_values(src_val, ew, relay, 1)
        inbox, stats = push_combined_flat_sharded(
            sg, edst, v, emask & src_act, worker, op,
            backend=backend, plan=plan)
    else:
        src_val = vals[jnp.arange(sg.m_loc)[:, None], esrc]
        src_act = active[jnp.arange(sg.m_loc)[:, None], esrc]
        v = relay_values(src_val, ew, relay, 2)
        inbox, stats = push_combined_sharded(sg, edst, v, emask & src_act,
                                             op, backend=backend, plan=plan)
    if use_mirroring:
        inbox2, s2 = push_mirror_sharded(sg, vals, active, op, relay,
                                         backend=backend)
        inbox = _MERGE[op](inbox, inbox2)
        stats.update(s2)
    with spans.scope(spans.STATS):
        if not use_mirroring:
            stats["msgs_mirror"] = jnp.zeros((), jnp.int32)
            stats["per_worker_mirror"] = jnp.zeros((sg.M,), jnp.int32)
        stats["msgs_total"] = stats["msgs_combined"] + stats["msgs_mirror"]
        stats["per_worker_total"] = (stats["per_worker_combined"]
                                     + stats["per_worker_mirror"])
    return inbox, stats


@spans.scope(spans.REQRESP)
def gather_sharded(sg: ShardedGraph, vals, targets, tmask,
                   dedup: bool = True):
    """Sharded Ch_req for row-shaped targets (m_loc, R): a real two-round
    trip — each worker's deduplicated requests route to the owner devices,
    owners answer from their local (m_loc, n_loc) shard, responses route
    back (``_routed_fetch``).  The request-respond *counts* (Theorem 3)
    are computed per device and psum-merged so they match the reference
    accounting exactly."""
    n_pad = sg.n_pad
    t = jnp.where(tmask, targets, n_pad)
    R = t.shape[1]
    if dedup:
        uniq, inv = jax.vmap(lambda r: _dedup_row(r, n_pad))(t)
    else:
        uniq = t
        inv = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32), t.shape)
    feat = planlib.feat_shape(vals, 2)
    flat_u = uniq.reshape(-1)
    got = _routed_fetch(sg, vals, flat_u, flat_u < n_pad
                        ).reshape(uniq.shape + feat)
    out = jnp.take_along_axis(got, planlib.feat_mask(inv, got, 2), axis=1)
    out = jnp.where(planlib.feat_mask(tmask, out, 2), out,
                    jnp.zeros((), vals.dtype))

    with spans.scope(spans.STATS):
        owner = jnp.clip(uniq // sg.n_loc, 0, sg.M - 1)
        uvalid = uniq < n_pad
        self_w = sg.worker_ids()[:, None]
        remote_u = uvalid & (owner != self_w)
        raw_remote = tmask & ((targets // sg.n_loc) != self_w)
        raw_owner = jnp.clip(targets // sg.n_loc, 0, sg.M - 1)
        stats = {
            "msgs_rr": 2 * jax.lax.psum(remote_u.sum(), sg.axis),
            "msgs_basic": 2 * jax.lax.psum(raw_remote.sum(), sg.axis),
            "per_worker_rr": (_place_rows(sg, remote_u.sum(1))
                              + _scatter_workers(sg, owner, remote_u)),
            "per_worker_basic": (
                _place_rows(sg, raw_remote.sum(1))
                + _scatter_workers(sg, raw_owner, raw_remote)),
        }
    return out, stats


@spans.scope(spans.REQRESP)
def gather_edges_sharded(sg: ShardedGraph, vals, targets, tmask,
                         dedup: bool = True):
    """Sharded Ch_req for edge-shaped targets (layout-dispatching).  The
    transport always rides the deduplicated (worker, target) segment heads
    — responses are propagated back down each segment — so the wire cost
    follows Theorem 3 regardless of the accounting mode requested."""
    if sg.layout != "csr":
        return gather_sharded(sg, vals, targets, tmask, dedup)
    n_pad = sg.n_pad
    worker = sg.all_pw if sg.split else sg.all_src // sg.n_loc
    wlog = sg.log_of(worker)
    t = jnp.where(tmask, targets, n_pad)
    L = t.shape[0]

    order, ws, ts, first = planlib.sort_by_worker_target(worker, t)
    heads = first & (ts < n_pad)
    cap = _cap_for(L, sg.D, sg.cap_hint) if sg.cap_hint else None
    head_vals = _routed_fetch(sg, vals, ts, heads, cap=cap)
    hidx = jax.lax.cummax(jnp.where(first, jnp.arange(L, dtype=jnp.int32),
                                    0))
    val_sorted = head_vals[hidx]
    feat = planlib.feat_shape(vals, 2)
    out = jnp.zeros((L,) + feat, vals.dtype).at[order].set(val_sorted)
    out = jnp.where(planlib.feat_mask(t < n_pad, out, 1), out,
                    jnp.zeros((), vals.dtype))

    with spans.scope(spans.STATS):
        owner = jnp.clip(targets // sg.n_loc, 0, sg.M - 1)
        raw_remote = tmask & ((targets // sg.n_loc) != wlog)
        if dedup:
            ws_log = sg.log_of(ws)
            uniq = heads
            remote_u = uniq & (ts // sg.n_loc != ws_log)
            u_w, u_owner = ws_log, jnp.clip(ts // sg.n_loc, 0, sg.M - 1)
        else:
            remote_u = raw_remote
            u_w, u_owner = wlog, owner
        stats = {
            "msgs_rr": 2 * jax.lax.psum(remote_u.sum(), sg.axis),
            "msgs_basic": 2 * jax.lax.psum(raw_remote.sum(), sg.axis),
            "per_worker_rr": (_scatter_workers(sg, u_w, remote_u)
                              + _scatter_workers(sg, u_owner, remote_u)),
            "per_worker_basic": (_scatter_workers(sg, wlog, raw_remote)
                                 + _scatter_workers(sg, owner, raw_remote)),
        }
    return out, stats


@spans.scope(spans.COMBINE)
def scatter_state_sharded(sg: ShardedGraph, base, targets, upd, mask,
                          op: str, backend: str = "dense"):
    """Sharded scatter-op for row-shaped runtime targets (S-V hooking).
    Runtime destinations admit no precomputed plan, so both backends share
    the sorted segmented combine + destination-routed exchange (the
    reference paths' stats are identical by construction, and min/max
    values are order-exact)."""
    with spans.scope(spans.STATS):
        gw = sg.worker_ids()[:, None]
        raw_cross = mask & ((targets // sg.n_loc) != gw)
        bstats = {"msgs_basic": jax.lax.psum(raw_cross.sum(), sg.axis),
                  "per_worker_basic": _place_rows(sg,
                                                  raw_cross.sum(axis=1))}
    inbox, (msgs, pw) = _combine_sorted_rows_sharded(sg, targets, upd,
                                                     mask, op)
    stats = {"msgs_combined": msgs, "per_worker_combined": pw}
    stats.update(bstats)
    return _MERGE[op](base, inbox), stats


@spans.scope(spans.COMBINE)
def scatter_edges_sharded(sg: ShardedGraph, base, targets, upd, mask,
                          op: str, backend: str = "dense"):
    """Sharded scatter-op for edge-shaped runtime targets (MSF election)."""
    if sg.layout != "csr":
        return scatter_state_sharded(sg, base, targets, upd, mask, op,
                                     backend)
    worker = sg.all_pw if sg.split else sg.all_src // sg.n_loc
    with spans.scope(spans.STATS):
        wlog = sg.log_of(worker)
        raw_cross = mask & ((targets // sg.n_loc) != wlog)
        bstats = {"msgs_basic": jax.lax.psum(raw_cross.sum(), sg.axis),
                  "per_worker_basic": _scatter_workers(sg, wlog,
                                                       raw_cross)}
    inbox, (msgs, pw) = _combine_sorted_flat_sharded(sg, targets, upd,
                                                     mask, worker, op)
    stats = {"msgs_combined": msgs, "per_worker_combined": pw}
    stats.update(bstats)
    return _MERGE[op](base, inbox), stats


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------

def _state_specs(tree, M: int, hier=None):
    row = P((HAXIS, AXIS)) if hier else P(AXIS)
    return jax.tree.map(
        lambda x: row if (getattr(x, "ndim", 0) >= 1
                          and x.shape[0] == M) else P(), tree)


def _acc_specs(stats_shape):
    """PartitionSpec pytree matching bsp's (hi, lo) limb accumulator."""
    return [
        (P(), P()) if jnp.issubdtype(leaf.dtype, jnp.integer) else P()
        for leaf in jax.tree.leaves(stats_shape)
    ]


def build_sharded(pg, make_step: Callable, state0, max_supersteps: int,
                  record_history: bool = False, devices: int = 1,
                  plan_kinds: Sequence[str] = (), pipeline: bool = False,
                  pipeline_chunks: Optional[int] = None,
                  profile: Optional[ShardProfile] = None,
                  on_trace: Optional[Callable] = None):
    """Build the jitted sharded BSP program.  Returns (fn, args) with
    ``fn(*args) == (final_state, raw_acc, n_supersteps, history)`` —
    fold ``raw_acc`` with ``finalize_stats`` (run_sharded does) to get
    the ``bsp.run`` totals contract.

    ``make_step(g)`` must build the superstep function against either a
    PartitionedGraph (used here only to trace the stats structure) or the
    device-local ShardedGraph.

    ``pipeline=True`` turns on the double-buffered superstep: every
    routed exchange is chunked (~``pipeline_chunks`` chunks, default
    ``DEFAULT_PIPELINE_CHUNKS`` on a multi-device mesh, 1 on a single
    device where the all_to_all is a local transpose and chunk overhead
    buys nothing) so chunk k's all_to_all overlaps chunk k-1's local
    combine, and the (hi, lo) stats fold is deferred one superstep
    (``bsp.run(pipeline=True)``).  Results keep the parity contract:
    min/max/int bitwise, stats integer-exact, float sums within the
    usual exchange-order tolerance.

    ``devices`` may also be an ``(hosts, per_host)`` pair: the program
    then runs on the 2-D mesh with the hierarchical two-leg exchanges
    (combine within the host, route the residue across hosts), same
    parity contract against the 1-D path.

    ``profile`` pads the shard arrays to a frozen :class:`ShardProfile`
    envelope so a resident program survives graph folds with ZERO
    re-traces (feed ``reshard_arrays`` outputs to the returned fn);
    ``on_trace`` is called (Python side effect) each time the inner
    program actually traces — the serving trace counter."""
    D, hier = _normalize_devices(devices)
    if pg.M % D:
        raise ValueError(f"M={pg.M} workers must divide over "
                         f"devices={devices}")
    mesh = graph_mesh(devices)
    meta, arrays, arr_specs = _shard_graph(pg, devices, plan_kinds,
                                           pipeline, pipeline_chunks)
    if profile is not None:
        _apply_profile(meta, arrays, profile)

    with spans.span(spans.TRACE):
        _, _, stats_shape = jax.eval_shape(make_step(pg), state0,
                                           jnp.zeros((), jnp.int32))
    st_specs = _state_specs(state0, pg.M, hier)
    stats_specs = jax.tree.map(lambda _: P(), stats_shape)
    hist_specs = stats_specs if record_history else None

    def inner(arrs, st0):
        if on_trace is not None:
            on_trace()
        sg = _make_sg(meta, arrs)
        return bsp.run(make_step(sg), st0, max_supersteps, record_history,
                       raw_totals=True, pipeline=pipeline)

    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=(arr_specs, st_specs),
                       out_specs=(st_specs, _acc_specs(stats_shape), P(),
                                  hist_specs),
                       check_vma=False)
    return jax.jit(fn), (arrays, state0), stats_shape


def finalize_stats(raw_acc, stats_shape):
    """Fold the limb accumulator returned by a ``build_sharded`` program
    into exact host-side totals (Python ints / numpy int64)."""
    _, treedef = jax.tree.flatten(stats_shape)
    return bsp.finalize_totals(raw_acc, treedef)


def run_sharded(pg, make_step: Callable, state0, max_supersteps: int,
                record_history: bool = False, devices: int = 1,
                plan_kinds: Sequence[str] = (), pipeline: bool = False,
                pipeline_chunks: Optional[int] = None):
    """Run a BSP program sharded over ``devices`` devices; same return
    contract as ``bsp.run`` (stats totals folded into exact host int64)."""
    fn, args, stats_shape = build_sharded(pg, make_step, state0,
                                          max_supersteps, record_history,
                                          devices, plan_kinds, pipeline,
                                          pipeline_chunks)
    with spans.span(spans.LAUNCH):
        st, raw_acc, n, hist = fn(*args)
    return st, finalize_stats(raw_acc, stats_shape), n, hist


def build_apply(pg, make_fn: Callable, args: Tuple, devices: int = 1,
                plan_kinds: Sequence[str] = (), pipeline: bool = False,
                pipeline_chunks: Optional[int] = None,
                out_rule: str = "rows",
                is_sharded: Optional[Callable] = None):
    """Build (but don't run) a one-shot sharded channel application:
    returns ``(fn, arrays)`` with ``fn(arrays, args) == make_fn(sg)(*args)``
    jitted once — callers that re-apply the same join with fresh ``args``
    (a training loop stepping the same graph) pay ONE compilation instead
    of one per call.  Input leaves with leading axis ``pg.M`` are
    worker-sharded, the rest replicated.  ``out_rule`` picks the output
    placement: ``"rows"`` (the historical contract) marks every ``out``
    leaf worker-sharded; ``"auto"`` keys each ``out`` leaf by the same
    leading-axis test as the inputs — what a mixed pytree of sharded
    row-state and replicated dense parameters (a training step) needs.
    ``is_sharded`` replaces the leading-axis test with a caller predicate
    (leaf -> bool) for pytrees where a replicated leaf's first dim could
    coincide with ``pg.M`` (e.g. a (M, hidden) weight matrix)."""
    D, hier = _normalize_devices(devices)
    if pg.M % D:
        raise ValueError(f"M={pg.M} workers must divide over "
                         f"devices={devices}")
    mesh = graph_mesh(devices)
    meta, arrays, arr_specs = _shard_graph(pg, devices, plan_kinds,
                                           pipeline, pipeline_chunks)
    row_spec = P((HAXIS, AXIS)) if hier else P(AXIS)

    def _spec_of(x):
        if is_sharded is not None:
            return row_spec if is_sharded(x) else P()
        return row_spec if (getattr(x, "ndim", 0) >= 1
                            and x.shape[0] == pg.M) else P()

    in_specs = jax.tree.map(_spec_of, args)
    out_shape, stats_shape = jax.eval_shape(make_fn(pg), *args)
    out_leaf = (_spec_of if out_rule == "auto"
                else (lambda _: row_spec))
    out_specs = (jax.tree.map(out_leaf, out_shape),
                 jax.tree.map(lambda _: P(), stats_shape))

    def inner(arrs, a):
        sg = _make_sg(meta, arrs)
        return make_fn(sg)(*a)

    fn = jax.shard_map(inner, mesh=mesh, in_specs=(arr_specs, in_specs),
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn), arrays


def apply_sharded(pg, make_fn: Callable, args: Tuple, devices: int = 1,
                  plan_kinds: Sequence[str] = (), pipeline: bool = False,
                  pipeline_chunks: Optional[int] = None):
    """One-shot sharded channel application (no BSP loop): ``make_fn(sg)``
    returns ``fn(*local_args) -> (out, stats)`` where every ``out`` leaf is
    worker/edge-sharded on its leading axis and ``stats`` is replicated.
    csr edge-shaped outputs come back device-concatenated with per-device
    padding — strip with ``csr_device_bounds``."""
    fn, arrays = build_apply(pg, make_fn, args, devices, plan_kinds,
                             pipeline, pipeline_chunks)
    return fn(arrays, args)


def exchange_volume_report(pg, devices, plan_kinds: Sequence[str] = ()):
    """Static per-superstep exchange-volume accounting from the shard
    tables (host-side; no compilation).  Counts the wire lanes of every
    static exchange the executor runs per superstep — the plan exchanges
    (Ch_msg/Ch_mir on the pallas backend) and the fetch plans (mirror
    values, split source reads):

    * 1-D mesh: every lane between two distinct devices is ``intra_host``
      (one host) and ``cross_host`` is 0 — ``total`` is the flat
      all-pairs volume the hierarchical gate compares against.
    * 2-D mesh: leg-1 lanes leaving their column (intra-host wire) count
      as ``intra_host``; leg-2 / leg-A lanes leaving their host count as
      ``cross_host``.  The intermediate combine means ``cross_host`` is
      the *post-combine residue* — the per-level Theorem-1 bound in
      action, and the number the bench gate requires to be strictly
      below the flat all-pairs volume."""
    D, hier = _normalize_devices(devices)
    meta, arrays, _ = _shard_graph(pg, devices, plan_kinds)
    dev = np.arange(D)
    rep = {"devices": D, "hier": hier, "per_exchange": {}}
    intra = cross = 0

    def add(name, i, c):
        rep["per_exchange"][name] = {"intra_host": int(i),
                                     "cross_host": int(c)}

    for kind in meta["plan_meta"]:
        if hier:
            H, T = hier
            snd1 = np.asarray(arrays[f"plan_{kind}_x1val"]).sum(axis=2)
            i_k = int(snd1[(dev % T)[:, None] != np.arange(T)[None]].sum())
            snd2 = np.asarray(arrays[f"plan_{kind}_x2val"]).sum(axis=2)
            c_k = int(snd2[(dev // T)[:, None] != np.arange(H)[None]].sum())
        else:
            snd = np.asarray(arrays[f"plan_{kind}_xval"]).sum(axis=2)
            i_k, c_k = int(snd.sum() - np.trace(snd)), 0
        add(f"plan_{kind}", i_k, c_k)
        intra, cross = intra + i_k, cross + c_k
    for name in meta["fetch_meta"]:
        if hier:
            H, T = hier
            a_snd = (np.asarray(arrays[f"fetch_{name}_a_send"]) >= 0
                     ).sum(axis=2)
            c_k = int(a_snd[(dev // T)[:, None] != np.arange(H)[None]].sum())
            b_snd = (np.asarray(arrays[f"fetch_{name}_b_send"]) >= 0
                     ).sum(axis=2)
            i_k = int(b_snd[(dev % T)[:, None] != np.arange(T)[None]].sum())
        else:
            snd = (np.asarray(arrays[f"fetch_{name}_send_slot"]) >= 0
                   ).sum(axis=2)
            i_k, c_k = int(snd.sum() - np.trace(snd)), 0
        add(f"fetch_{name}", i_k, c_k)
        intra, cross = intra + i_k, cross + c_k
    rep.update(intra_host=intra, cross_host=cross, total=intra + cross)
    return rep

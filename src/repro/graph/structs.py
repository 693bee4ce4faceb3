"""Graph containers and the worker-partitioned representation.

Design note (hardware adaptation, DESIGN.md §2): the engine executes the
paper's per-worker logic as *batched* JAX ops over a leading worker axis
``M``.  On one CPU device that axis is a plain batch dimension (exact
M-worker simulation, exact message counts); under ``jit`` with the axis
sharded over a TPU mesh the very same code lowers to all-to-all /
all-gather collectives (the multi-pod dry-run proves it).  Static shapes
come from padding each per-worker array to the max across workers — the
padding itself visualizes the skew the paper fights.

Two edge layouts are supported (``partition(..., layout=...)``):

* ``"padded"`` — the reference layout: per-worker edge rows padded to the
  hottest worker's length, ``(M, E_hot)`` arrays.  O(M * E_hot) host
  memory; one skewed worker pads every row.
* ``"csr"``    — flat edge arrays ``(E,)`` plus per-worker ``(M+1,)`` row
  offsets (``eg_off``/``all_off``/``mir_eoff``).  O(E + M + n) memory,
  no hot-worker padding; destination-blockable by ``core/plan.py``
  without any intermediate padded unpack.  In this layout ``eg_src`` /
  ``all_src`` hold *global* source slot ids (owner derivable as
  ``src // n_loc``) and ``mir_edst`` holds *global* destination ids
  (hosting worker derivable the same way).

Vertex ids are relabeled at partition time and then block-partitioned:
``owner(v) = v // n_loc`` with O(1) owner computation.  The relabeling
is the load-balancing/locality knob (``partition(..., balance=...)``),
resolved through the pluggable partitioner layer in
``graph/partitioner.py`` (``Partitioner.assign(g, M, hosts) ->
(perm, split_spec)``):

* ``"hash"``  — a random permutation: distributionally identical to
  Pregel's hash partitioning (the reference baseline).
* ``"edges"`` — greedy edge-count-balanced assignment: vertices are priced
  by ``core/cost_model.vertex_cost`` (local edges + the Theorem-1 message
  bound) and packed LPT-style onto workers, each worker's vertices taking
  consecutive ids in its block.  Fixes multi-vertex skew; a single vertex
  hotter than a whole worker's fair share still creates a straggler.
* ``"edges+refine"`` — ``"edges"`` plus a greedy locality refinement
  pass (``cost_model.refine_assignment``) that strictly descends the
  ``pair_counts`` crossness objective under the same slot/load caps —
  fewer distinct cross-worker message pairs at equal balance.
* ``"vertex-cut"`` — ``"edges"`` plus mega-hub state-row splitting:
  vertices whose degree exceeds ``split_factor * m / M`` are force-
  mirrored (``tau_eff`` is lowered to the cut threshold), so their
  fan-out rows shard across the destination workers with the
  master/replica mirror combine — the remaining single-vertex
  straggler ``"split"`` can only shard at the edge-range level.
* ``"split"`` — ``"edges"`` plus hot-worker splitting (csr layout only):
  workers whose edge load exceeds ``split_factor x`` the mean are split
  into equal-edge-count *physical shards* by moving csr row-offset
  boundaries (``phys_*_off`` refine the per-worker offsets; ``phys_log``
  maps shards back to logical workers).  Sender-side combining and the
  Theorem-3 request dedup then run per physical shard — exactly what a
  real deployment's split worker does — while cross-worker message stats
  stay reported per *logical* worker, and ``core/exec.py`` places device
  boundaries between shards so per-device edge loads balance even under
  extreme degree skew.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax.numpy as jnp

from repro.core import cost_model
from repro.graph import partitioner as partitioner_mod
from repro.graph.partitioner import BALANCES  # noqa: F401 (re-export)

LAYOUTS = ("padded", "csr")


@dataclasses.dataclass
class Graph:
    """Host-side graph: COO edge list (directed; undirected graphs store both
    directions)."""
    n: int
    src: np.ndarray  # (E,) int64
    dst: np.ndarray  # (E,) int64
    weight: Optional[np.ndarray] = None  # (E,) float32

    @property
    def m(self) -> int:
        return len(self.src)

    def out_degrees(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n)

    def symmetrized(self) -> "Graph":
        """Both directions, deduplicated; undirected weights canonicalized
        to the min over the two directions (so w(a,b) == w(b,a))."""
        src = np.concatenate([self.src, self.dst])
        dst = np.concatenate([self.dst, self.src])
        w = None if self.weight is None else np.concatenate([self.weight] * 2)
        key = src.astype(np.int64) * self.n + dst
        order = np.argsort(key, kind="stable")
        key_s, src_s, dst_s = key[order], src[order], dst[order]
        first = np.concatenate([[True], key_s[1:] != key_s[:-1]])
        src_u, dst_u = src_s[first], dst_s[first]
        if w is None:
            return Graph(self.n, src_u, dst_u, None)
        wmin_dir = np.minimum.reduceat(w[order], np.flatnonzero(first))
        lo = np.minimum(src_u, dst_u)
        hi = np.maximum(src_u, dst_u)
        ukey = lo.astype(np.int64) * self.n + hi
        _, inv = np.unique(ukey, return_inverse=True)
        wpair = np.full(inv.max() + 1, np.inf, np.float32)
        np.minimum.at(wpair, inv, wmin_dir.astype(np.float32))
        return Graph(self.n, src_u, dst_u, wpair[inv].astype(np.float32))


@dataclasses.dataclass
class PartitionedGraph:
    """M-worker partition with the paper's two channels precomputed.

    Low-degree (< tau) vertices' edges go through Ch_msg (COO per worker);
    high-degree vertices are *mirrored*: their value is broadcast once per
    hosting worker and fanned out locally through the mirror COO.

    ``layout="padded"``: edge arrays are (M, E_loc) rows padded to the
    hottest worker.  ``layout="csr"``: edge arrays are flat (E,) with
    per-worker (M+1,) row offsets; ``eg_src``/``all_src`` hold *global*
    source slots and ``mir_edst`` *global* destination ids (the worker of
    an edge is ``id // n_loc``), masks are all-True (no padding exists).

    Edge-sized arrays (``eg_*``, ``all_*``, ``mir_e*``, ``*_pw``) stay host
    NumPy: the sharded executor slices them per device and each device
    receives only its slice, so no device ever holds the whole edge set.
    Vertex-sized arrays are jnp.
    """
    n: int
    M: int
    n_loc: int
    tau: int
    perm: np.ndarray          # relabel: new_id = perm[old_id]
    inv_perm: np.ndarray

    # Ch_msg edges (from non-mirrored sources):
    eg_src: np.ndarray       # (M, E_loc) local src slot | (E_lo,) global
    eg_dst: np.ndarray       # (M, E_loc) global dst id (pad: 0) | (E_lo,)
    eg_mask: np.ndarray      # (M, E_loc) bool | (E_lo,) all-True
    eg_w: np.ndarray         # (M, E_loc) float32 | (E_lo,)

    # full adjacency (mirrored + not), for algorithms that need all edges:
    all_src: np.ndarray      # (M, A_loc) | (E,) global
    all_dst: np.ndarray
    all_mask: np.ndarray
    all_w: np.ndarray

    # mirror structures:
    mir_ids: jnp.ndarray      # (n_mir,) global ids of mirrored vertices (pad n)
    mir_slot_of: jnp.ndarray  # (M, n_loc) index into mir_ids or -1
    mir_nworkers: jnp.ndarray # (n_mir,) #workers holding a mirror (Thm 1 count)
    mir_esrc: np.ndarray     # (M, ME_loc) index into mir_ids | (ME,)
    mir_edst: np.ndarray     # (M, ME_loc) local dst slot | (ME,) global dst
    mir_emask: np.ndarray    # (M, ME_loc) | (ME,) all-True
    mir_ew: np.ndarray       # (M, ME_loc) | (ME,)

    deg: jnp.ndarray          # (M, n_loc) out-degree
    vmask: jnp.ndarray        # (M, n_loc) real-vertex mask

    layout: str = "padded"
    # csr row offsets (host numpy, (M+1,) int64); None in padded layout:
    eg_off: Optional[np.ndarray] = None
    all_off: Optional[np.ndarray] = None
    mir_eoff: Optional[np.ndarray] = None

    # -- load balancing (partition(..., balance=...)) ---------------------
    balance: str = "hash"
    split_factor: float = 1.2
    # physical worker axis (balance="split"): hot workers are split into
    # equal-edge-count shards; M_phys == M and phys_log is None otherwise.
    M_phys: int = 0
    phys_log: Optional[np.ndarray] = None      # (M_phys,) logical worker
    phys_eg_off: Optional[np.ndarray] = None   # (M_phys+1,) refined offsets
    phys_all_off: Optional[np.ndarray] = None
    phys_mir_off: Optional[np.ndarray] = None
    eg_pw: Optional[np.ndarray] = None        # per-edge physical shard ids
    all_pw: Optional[np.ndarray] = None
    mir_pw: Optional[np.ndarray] = None

    # (M, M) distinct (source worker, destination vertex) pair counts of
    # the full adjacency: pair_counts[s, d] bounds the combined messages
    # worker s can ever route to worker d in one superstep.  The sharded
    # executor folds worker blocks into per-device-pair caps so the
    # routed all_to_all exchanges are sized from the graph, not guessed.
    pair_counts: Optional[np.ndarray] = None

    # host-topology-aware placement (partition(..., hosts=H)): workers
    # were relabeled so block [h*M/H, (h+1)*M/H) is host h's — heavy-
    # communicating pairs (incl. mirror broadcasts) land intra-host on a
    # hierarchical (H, T) device mesh.  None = host-oblivious order.
    hosts: Optional[int] = None

    # lazily-built message plans: core/plan.get_plan's, keyed (kind, nb,
    # eb), and the sharded executor's stacked per-device plans
    # (core/exec._sharded_plan, read-only arrays), keyed ("sharded", kind,
    # D, hier, nb, pipeline chunks).  Valid because the instance is never
    # mutated (fold_delta / repartition return a new one with an empty
    # cache); per-instance scratch, never part of equality or the pytree.
    plan_cache: dict = dataclasses.field(default_factory=dict, repr=False,
                                         compare=False)

    @property
    def n_pad(self) -> int:
        return self.M * self.n_loc

    def edge_load(self, phys: bool = False) -> np.ndarray:
        """Per-worker edge load: Ch_msg edges stored at the source worker
        plus mirror fan-out edges at the hosting worker (== the full
        adjacency count when mirroring is off).  ``phys=True`` returns the
        per-physical-shard loads of a split partition."""
        if self.layout == "csr":
            if phys and self.phys_log is not None:
                return (np.diff(self.phys_eg_off)
                        + np.diff(self.phys_mir_off))
            return np.diff(self.eg_off) + np.diff(self.mir_eoff)
        return (np.asarray(self.eg_mask).sum(axis=1)
                + np.asarray(self.mir_emask).sum(axis=1)).astype(np.int64)

    def local_ids(self) -> jnp.ndarray:
        """(M, n_loc) global id of each local slot."""
        return (jnp.arange(self.M)[:, None] * self.n_loc
                + jnp.arange(self.n_loc)[None, :])

    # -- global reductions ------------------------------------------------
    # On one device these are plain jnp reductions; the sharded executor's
    # ``ShardedGraph`` (core/exec.py) overrides them with cross-device
    # collectives so algorithm code (halt votes, aggregators) is written
    # once and runs identically under ``shard_map``.
    def gany(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.any(x)

    def gall(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.all(x)

    def gsum(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(x)

    def gmax(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.max(x)

    def edge_src_values(self, state: jnp.ndarray, src: jnp.ndarray
                        ) -> jnp.ndarray:
        """Read per-vertex ``state`` at each edge's (locally stored) source
        endpoint, for either edge layout: ``src`` is (M, E_loc) local slots
        in the padded layout, flat (E,) global slot ids in csr."""
        if self.layout == "csr":
            return state.reshape(-1)[src]
        return state[jnp.arange(state.shape[0])[:, None], src]


def _pad_rows(rows, pad_val, dtype):
    """list of 1-D arrays -> (M, maxlen) + mask."""
    m = max((len(r) for r in rows), default=0)
    m = max(m, 1)
    out = np.full((len(rows), m), pad_val, dtype=dtype)
    mask = np.zeros((len(rows), m), bool)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
        mask[i, :len(r)] = True
    return out, mask


def canonical_labels(pg: PartitionedGraph, labels) -> np.ndarray:
    """Group labels computed in *relabeled* space (e.g. Hash-Min / S-V
    component ids, which are min relabeled ids) -> per-original-vertex
    canonical representative: the min ORIGINAL id of each group.  Makes
    results comparable across balance modes, which permute differently."""
    flat = np.asarray(labels).reshape(-1)
    lab = flat[pg.perm]
    uniq, inv = np.unique(lab, return_inverse=True)
    rep = np.full(len(uniq), pg.n, np.int64)
    np.minimum.at(rep, inv, np.arange(pg.n))
    return rep[inv]


def _refine_offsets(off: np.ndarray, k: np.ndarray) -> np.ndarray:
    """Split each worker's [off[w], off[w+1]) edge range into k[w] near
    equal parts -> (sum(k)+1,) physical offsets refining ``off``."""
    off = np.asarray(off, np.int64)
    starts = np.repeat(off[:-1], k)
    lens = np.repeat(np.diff(off), k)
    kk = np.repeat(k, k)
    jj = (np.arange(int(k.sum()), dtype=np.int64)
          - np.repeat(np.cumsum(k) - k, k))
    return np.append(starts + (lens * jj) // kk, off[-1])


def partition(g: Graph, M: int, tau: Optional[int] = None,
              seed: int = 0, layout: str = "padded",
              balance: str = "hash",
              split_factor: float = 1.2,
              hosts: Optional[int] = None,
              perm: Optional[np.ndarray] = None) -> PartitionedGraph:
    """Partition ``g`` over M workers with mirroring threshold ``tau``
    (None => mirroring disabled, i.e. tau = inf).

    ``layout="padded"`` builds (M, E_hot) per-worker rows (reference);
    ``layout="csr"`` builds flat (E,) arrays + (M+1,) row offsets —
    O(E + M + n) host memory, no hot-worker padding.  Both layouts come
    from the same single stable sort, so corresponding edge orders are
    identical (csr == padded rows concatenated without the padding).

    ``balance`` resolves through the pluggable partitioner layer
    (``graph/partitioner.py`` — ``partitioner_for(balance).assign(g, M,
    hosts) -> (perm, split_spec)``): ``"hash"`` random, ``"edges"``
    greedy edge-balanced, ``"edges+refine"`` edge-balanced plus the
    greedy crossness-descent locality pass, ``"split"`` edge-balanced
    plus physical splitting of workers whose edge load exceeds
    ``split_factor x`` the mean (csr only), ``"vertex-cut"``
    edge-balanced plus forced mirroring of vertices whose degree
    exceeds ``split_factor * m / M`` (mega-hub state rows shard across
    the destination workers via the master/replica mirror combine).

    ``hosts=H`` makes the placement host-topology-aware for the
    hierarchical (H, T) device mesh: after the balance assignment the M
    workers are regrouped (``cost_model.affinity_groups`` over the
    worker-pair traffic matrix) so heavy-communicating pairs — combined
    residue and mirror broadcasts alike; a split worker's physical
    shards stay contiguous inside its logical block — land in the same
    host block of M/H workers, i.e. on the same host once the executor
    maps worker blocks onto the mesh.  Placement only: results are
    bitwise identical to the host-oblivious partition after
    ``canonical_labels``.

    ``perm`` pins the vertex relabeling (``new_id = perm[old_id]``)
    instead of deriving it from ``seed``/``balance``/``hosts`` — used by
    the delta-fold reference path and parity tests, where the mutated
    graph must land in exactly the placement of an existing partition.
    The host-affinity regroup is skipped too: an explicit perm is final.
    """
    if layout not in LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}; use one of {LAYOUTS}")
    if balance not in BALANCES:
        raise ValueError(f"unknown balance {balance!r}; use one of "
                         f"{BALANCES}")
    if balance == "split" and layout != "csr":
        raise ValueError('balance="split" moves csr row-offset boundaries; '
                         'use layout="csr"')
    n_loc = -(-g.n // M)
    pinned_perm = perm is not None
    tau_eff = tau if tau is not None else g.n + 1
    if pinned_perm:
        # an explicit perm is final: the partitioner layer (and the
        # host regroup) is bypassed, and ``tau`` must already be the
        # EFFECTIVE threshold (``pg.tau`` embeds the vertex-cut fold)
        perm = np.asarray(perm, np.int64)
        if perm.shape != (g.n,):
            raise ValueError(f"perm must have shape ({g.n},), got "
                             f"{perm.shape}")
    else:
        p9r = partitioner_mod.partitioner_for(
            balance, tau=tau, seed=seed, split_factor=split_factor)
        perm, spec = p9r.assign(g, M, hosts)
        if spec.vc_thresh is not None:
            tau_eff = min(tau_eff, int(spec.vc_thresh))
    n_ids = M * n_loc
    inv = np.full(n_ids, -1, np.int64)
    inv[perm] = np.arange(g.n)
    src = perm[g.src]
    dst = perm[g.dst]
    w = g.weight if g.weight is not None else np.ones(g.m, np.float32)

    owner = src // n_loc
    deg = np.bincount(src, minlength=n_ids)
    mirrored = deg >= tau_eff                      # per (new) vertex id

    # ---- Ch_msg edges: sources below threshold -------------------------
    # one stable sort by owner, then per-worker slices (vectorized: the
    # old per-worker boolean scans were O(M * E))
    lo = ~mirrored[src]
    oorder = np.argsort(owner, kind="stable")
    osrc, odst, ow_, olo = src[oorder], dst[oorder], w[oorder], lo[oorder]
    bounds = np.searchsorted(owner[oorder], np.arange(M + 1))
    if layout == "csr":
        # flat arrays in the exact per-worker order of the padded rows;
        # global source slot ids (owner == src // n_loc by construction)
        all_src = osrc.astype(np.int32)
        all_dst = odst.astype(np.int32)
        all_w = ow_.astype(np.float32)
        all_mask = np.ones(len(osrc), bool)
        all_off = bounds.astype(np.int64)
        eg_src = osrc[olo].astype(np.int32)
        eg_dst = odst[olo].astype(np.int32)
        eg_w = ow_[olo].astype(np.float32)
        eg_mask = np.ones(len(eg_src), bool)
        eg_off = np.searchsorted(owner[oorder][olo],
                                 np.arange(M + 1)).astype(np.int64)
    else:
        eg_rows_s, eg_rows_d, eg_rows_w = [], [], []
        all_rows_s, all_rows_d, all_rows_w = [], [], []
        for wk in range(M):
            sl = slice(bounds[wk], bounds[wk + 1])
            all_rows_s.append((osrc[sl] % n_loc).astype(np.int32))
            all_rows_d.append(odst[sl].astype(np.int32))
            all_rows_w.append(ow_[sl].astype(np.float32))
            keep = olo[sl]
            eg_rows_s.append((osrc[sl][keep] % n_loc).astype(np.int32))
            eg_rows_d.append(odst[sl][keep].astype(np.int32))
            eg_rows_w.append(ow_[sl][keep].astype(np.float32))
        eg_src, eg_mask = _pad_rows(eg_rows_s, 0, np.int32)
        eg_dst, _ = _pad_rows(eg_rows_d, 0, np.int32)
        eg_w, _ = _pad_rows(eg_rows_w, 0.0, np.float32)
        all_src, all_mask = _pad_rows(all_rows_s, 0, np.int32)
        all_dst, _ = _pad_rows(all_rows_d, 0, np.int32)
        all_w, _ = _pad_rows(all_rows_w, 0.0, np.float32)
        eg_off = all_off = None

    # ---- mirrors: group each high-deg vertex's edges by dst worker -----
    mir_vertex_ids = np.flatnonzero(mirrored)          # sorted global ids
    n_mir = max(len(mir_vertex_ids), 1)
    mir_slot_of = np.full((M, n_loc), -1, np.int32)
    mir_slot_of.reshape(-1)[mir_vertex_ids] = np.arange(len(mir_vertex_ids))

    hi = mirrored[src]
    hsrc, hdst, hw = src[hi], dst[hi], w[hi]
    dst_owner = hdst // n_loc
    es_all = np.zeros(0, np.int32)
    edg_all = np.zeros(0, np.int64)                    # global dst ids
    ew_all = np.zeros(0, np.float32)
    hb = np.zeros(M + 1, np.int64)
    nworkers = np.zeros(n_mir, np.int64)
    if len(hsrc):
        # vectorized grouping: sort once by (dst worker, src, dst), then
        # slice per hosting worker (was a Python loop over every edge)
        order = np.lexsort((hdst, hsrc, dst_owner))
        hsrc, hdst, hw, dst_owner = (hsrc[order], hdst[order], hw[order],
                                     dst_owner[order])
        mir_idx_of = np.full(n_ids, -1, np.int64)
        mir_idx_of[mir_vertex_ids] = np.arange(len(mir_vertex_ids))
        es_all = mir_idx_of[hsrc].astype(np.int32)
        edg_all = hdst.astype(np.int64)
        ew_all = hw.astype(np.float32)
        hb = np.searchsorted(dst_owner, np.arange(M + 1)).astype(np.int64)
        # workers per mirrored vertex
        pair = np.unique(hsrc * np.int64(M) + dst_owner)
        cnt = np.bincount((pair // M).astype(np.int64), minlength=n_ids)
        nworkers = cnt[mir_vertex_ids] if len(mir_vertex_ids) else nworkers
    if layout == "csr":
        mir_esrc = es_all
        mir_edst = edg_all.astype(np.int32)            # global dst ids
        mir_ew = ew_all
        mir_emask = np.ones(len(es_all), bool)
        mir_eoff = hb
    else:
        rows_es = [es_all[hb[ow]:hb[ow + 1]] for ow in range(M)]
        rows_ed = [(edg_all[hb[ow]:hb[ow + 1]] % n_loc).astype(np.int32)
                   for ow in range(M)]
        rows_ew = [ew_all[hb[ow]:hb[ow + 1]] for ow in range(M)]
        mir_esrc, mir_emask = _pad_rows(rows_es, 0, np.int32)
        mir_edst, _ = _pad_rows(rows_ed, 0, np.int32)
        mir_ew, _ = _pad_rows(rows_ew, 0.0, np.float32)
        mir_eoff = None

    deg_pad = deg.astype(np.int32).reshape(M, n_loc)
    vmask = np.zeros((M, n_loc), bool)
    vmask.reshape(-1)[perm] = True

    # per-destination caps (Theorem-1-style static bound): distinct
    # (source worker, destination vertex) pairs per worker pair — one
    # unique over the edge list, O(E log E) like the layout sorts above
    pkey = np.unique(owner.astype(np.int64) * n_ids + dst)
    pair_counts = np.zeros((M, M), np.int64)
    np.add.at(pair_counts,
              ((pkey // n_ids).astype(np.int64),
               ((pkey % n_ids) // n_loc).astype(np.int64)), 1)

    mir_ids_arr = np.full(n_mir, M * n_loc, np.int32)
    mir_ids_arr[:len(mir_vertex_ids)] = mir_vertex_ids

    # ---- hot-worker splitting: physical shard boundaries ---------------
    M_phys, phys_log = M, None
    phys_eg = phys_all = phys_mir = None
    eg_pw = all_pw = mir_pw = None
    if balance == "split":
        load = np.diff(eg_off) + np.diff(hb)
        k = cost_model.choose_split(load, split_factor)
        M_phys = int(k.sum())
        phys_log = np.repeat(np.arange(M, dtype=np.int64), k)
        phys_eg = _refine_offsets(eg_off, k)
        phys_all = _refine_offsets(all_off, k)
        phys_mir = _refine_offsets(hb, k)
        pids = np.arange(M_phys, dtype=np.int32)
        eg_pw = np.repeat(pids, np.diff(phys_eg))
        all_pw = np.repeat(pids, np.diff(phys_all))
        mir_pw = np.repeat(pids, np.diff(phys_mir))
        if len(hsrc):
            # Theorem-1 accounting at shard granularity: a mirrored vertex
            # is broadcast once per *physical shard* hosting its edges
            spair = np.unique(es_all.astype(np.int64) * M_phys + mir_pw)
            nworkers = np.bincount(spair // M_phys, minlength=n_mir)

    return PartitionedGraph(
        n=g.n, M=M, n_loc=n_loc, tau=int(tau_eff), perm=perm, inv_perm=inv,
        eg_src=eg_src, eg_dst=eg_dst, eg_mask=eg_mask, eg_w=eg_w,
        all_src=all_src, all_dst=all_dst, all_mask=all_mask, all_w=all_w,
        mir_ids=jnp.asarray(mir_ids_arr),
        mir_slot_of=jnp.asarray(mir_slot_of),
        mir_nworkers=jnp.asarray(nworkers),
        mir_esrc=mir_esrc, mir_edst=mir_edst, mir_emask=mir_emask,
        mir_ew=mir_ew,
        deg=jnp.asarray(deg_pad), vmask=jnp.asarray(vmask),
        layout=layout, eg_off=eg_off, all_off=all_off, mir_eoff=mir_eoff,
        balance=balance, split_factor=split_factor, M_phys=M_phys,
        phys_log=phys_log, phys_eg_off=phys_eg, phys_all_off=phys_all,
        phys_mir_off=phys_mir, eg_pw=eg_pw, all_pw=all_pw, mir_pw=mir_pw,
        pair_counts=pair_counts, hosts=hosts,
    )


# ---------------------------------------------------------------------------
# Streaming mutations: delta-CSR segments folded into the flat layout
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EdgeDelta:
    """A streaming mutation batch, in ORIGINAL vertex-id space.

    ``add_*`` are appended as-is (parallel edges allowed, like the base
    edge list); ``rem_*`` remove every stored edge matching the (src,
    dst) pair, whatever its weight.  The vertex-id universe is fixed at
    partition time: deltas may only reference ids < n (size the graph
    with isolated vertices up front to "add" vertices later).
    """
    add_src: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    add_dst: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    add_w: Optional[np.ndarray] = None
    rem_src: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))
    rem_dst: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64))

    def symmetrized(self) -> "EdgeDelta":
        """Both directions of every add and removal (for graphs stored
        symmetrized).  No dedup: don't add (u, v) and (v, u) both."""
        w = None if self.add_w is None else np.concatenate([self.add_w] * 2)
        return EdgeDelta(
            add_src=np.concatenate([self.add_src, self.add_dst]),
            add_dst=np.concatenate([self.add_dst, self.add_src]),
            add_w=w,
            rem_src=np.concatenate([self.rem_src, self.rem_dst]),
            rem_dst=np.concatenate([self.rem_dst, self.rem_src]))


def apply_delta(g: Graph, delta: EdgeDelta) -> Graph:
    """Host reference mutation: kept edges in original order, adds
    appended.  ``fold_delta`` on a partition of ``g`` must equal
    ``partition(apply_delta(g, delta), ..., perm=pg.perm)``."""
    keep = np.ones(g.m, bool)
    if len(delta.rem_src):
        rkey = (np.asarray(delta.rem_src, np.int64) * g.n
                + np.asarray(delta.rem_dst, np.int64))
        keep = ~np.isin(g.src.astype(np.int64) * g.n + g.dst, rkey)
    a_src = np.asarray(delta.add_src, np.int64)
    a_dst = np.asarray(delta.add_dst, np.int64)
    src = np.concatenate([g.src[keep], a_src])
    dst = np.concatenate([g.dst[keep], a_dst])
    if g.weight is None and delta.add_w is None:
        return Graph(g.n, src, dst, None)
    w_old = (g.weight if g.weight is not None
             else np.ones(g.m, np.float32))
    a_w = (np.asarray(delta.add_w, np.float32) if delta.add_w is not None
           else np.ones(len(a_src), np.float32))
    return Graph(g.n, src, dst,
                 np.concatenate([w_old[keep], a_w]).astype(np.float32))


def _graph_of(pg: PartitionedGraph) -> Graph:
    """Reconstruct the original-id-space edge list stored in ``pg`` (csr:
    exact original within-worker order; padded: owner-grouped order)."""
    if pg.layout == "csr":
        s_new = np.asarray(pg.all_src, np.int64)
        d_new = np.asarray(pg.all_dst, np.int64)
        w = np.asarray(pg.all_w, np.float32)
    else:
        m = np.asarray(pg.all_mask)
        row = np.nonzero(m)[0]
        s_new = row * pg.n_loc + np.asarray(pg.all_src)[m].astype(np.int64)
        d_new = np.asarray(pg.all_dst)[m].astype(np.int64)
        w = np.asarray(pg.all_w)[m].astype(np.float32)
    return Graph(pg.n, pg.inv_perm[s_new], pg.inv_perm[d_new], w)


def _fold_rebuild(pg: PartitionedGraph, delta: EdgeDelta
                  ) -> PartitionedGraph:
    """Reference fold: materialize the mutated edge list and re-partition
    under the PINNED perm (placement identical, so resident executors
    keep their shapes).  Used for the padded layout and balance="split",
    whose physical shard boundaries are a global function of the loads."""
    g2 = apply_delta(_graph_of(pg), delta)
    return partition(g2, pg.M, tau=pg.tau, layout=pg.layout,
                     balance=pg.balance, split_factor=pg.split_factor,
                     hosts=pg.hosts, perm=pg.perm)


def fold_delta(pg: PartitionedGraph, delta: EdgeDelta) -> PartitionedGraph:
    """Fold a streaming edge delta into the flat csr layout WITHOUT
    re-running ``partition()`` — the serving-path mutation primitive.

    The vertex relabeling (``perm``), worker count, ``n_loc``, ``tau``
    and ``vmask`` are all preserved, so a resident sharded executor built
    on ``pg`` keeps its compiled shapes (modulo edge-count growth, which
    ``core/exec.ShardProfile`` absorbs).  The incremental work is O(E)
    passes plus O(|delta| log |delta|) sorts — never the O(E log E)
    global sorts or the greedy LPT assignment of a fresh ``partition()``:

    * full adjacency: removals are mask-compacted in place (kept edges
      stay owner-grouped in their original relative order), adds are
      counting-sorted by owner and appended to each owner's segment —
      exactly where a fresh stable owner-sort of [kept..., adds...]
      would put them, so the csr arrays match a fresh partition
      BITWISE;
    * Ch_msg (eg): recompacted from the merged adjacency by the new
      mirrored mask (degree flips across tau move edges between the
      channels);
    * mirror csr: kept mirror edges are already (dst_worker, src, dst)-
      sorted; the pool of incoming edges (adds with mirrored sources +
      lo->hi flipped vertices' edges) is sorted alone and merged via
      two searchsorted passes;
    * ``mir_nworkers`` (Theorem-1 counts): copied for untouched
      vertices, recomputed from the merged edges only for sources the
      delta or a tau flip touched;
    * ``pair_counts`` caps: monotone UPPER bound — distinct added
      (worker, dst) pairs increment, removals never decrement.  Caps
      may over-provision after churn but can never under-admit (and an
      under-capped exchange only costs overflow rounds, never
      correctness); an elastic ``GraphService.repartition()`` (or any
      fresh ``partition()``) re-tightens them to exact fresh-partition
      values.

    The padded layout and ``balance="split"`` fall back to the pinned-
    perm rebuild (``_fold_rebuild``).
    """
    if pg.layout != "csr" or pg.balance == "split":
        return _fold_rebuild(pg, delta)
    M, n_loc = pg.M, pg.n_loc
    n_ids = M * n_loc
    perm = pg.perm
    tau_eff = pg.tau

    a_src = perm[np.asarray(delta.add_src, np.int64)]
    a_dst = perm[np.asarray(delta.add_dst, np.int64)]
    a_w = (np.asarray(delta.add_w, np.float32)
           if delta.add_w is not None
           else np.ones(len(a_src), np.float32))
    rkey = None
    if len(delta.rem_src):
        rkey = np.unique(perm[np.asarray(delta.rem_src, np.int64)]
                         * n_ids
                         + perm[np.asarray(delta.rem_dst, np.int64)])
        # endpoint tables + hashed-key bitmap prefilter: the exact
        # (sorted-rkey) probe only runs on edges sharing BOTH endpoints
        # with some removal — np.isin would sort all E keys every fold
        t_src = np.zeros(n_ids, bool)
        t_dst = np.zeros(n_ids, bool)
        t_src[(rkey // n_ids)] = True
        t_dst[(rkey % n_ids)] = True
        _hb = np.uint64(64 - 22)            # 4M-entry bitmap
        h_mul = np.uint64(0x9E3779B97F4A7C15)
        h_bit = np.zeros(1 << 22, bool)
        h_bit[((rkey.astype(np.uint64) * h_mul)
               >> _hb).astype(np.int64)] = True

    def _removed(s, d):
        """Indices into (s, d) of edges matching a removal key."""
        if rkey is None or not len(s):
            return np.zeros(0, np.int64)
        c1 = np.flatnonzero(t_src[s])
        ci = c1[t_dst[d[c1]]]
        ck = s[ci].astype(np.int64) * n_ids + d[ci]
        hh = h_bit[((ck.astype(np.uint64) * h_mul)
                    >> _hb).astype(np.int64)]
        ci, ck = ci[hh], ck[hh]
        p = np.searchsorted(rkey, ck)
        p[p == len(rkey)] = 0           # ck > rkey[-1] there: no match
        return ci[rkey[p] == ck]

    all_src = np.asarray(pg.all_src)          # int32, zero-copy views
    all_dst = np.asarray(pg.all_dst)
    all_w = np.asarray(pg.all_w)
    all_off = np.asarray(pg.all_off, np.int64)
    rem_idx = _removed(all_src, all_dst)
    keep = np.ones(len(all_src), bool)
    keep[rem_idx] = False

    deg_old = np.asarray(pg.deg, np.int64).reshape(-1)
    deg_new = (deg_old
               - np.bincount(all_src[rem_idx], minlength=n_ids)
               + np.bincount(a_src, minlength=n_ids))

    # ---- merged full adjacency: kept edges compact in place, adds
    #      counting-sorted by owner and appended per owner segment ------
    rem_owner = np.searchsorted(all_off, rem_idx, side="right") - 1
    a_owner = a_src // n_loc
    ao = np.argsort(a_owner, kind="stable")
    a_src, a_dst, a_w, a_owner = a_src[ao], a_dst[ao], a_w[ao], a_owner[ao]
    kept_cnt = np.diff(all_off) - np.bincount(rem_owner, minlength=M)
    add_cnt = np.bincount(a_owner, minlength=M)
    ad_off = np.concatenate([[0], np.cumsum(add_cnt)]).astype(np.int64)
    new_off = np.concatenate(
        [[0], np.cumsum(kept_cnt + add_cnt)]).astype(np.int64)
    e_new = int(new_off[-1])
    a_src32 = a_src.astype(np.int32)
    a_dst32 = a_dst.astype(np.int32)
    no_rem = not len(rem_idx)

    def _merge(vals, add, dtype):
        # [kept_0, add_0, kept_1, add_1, ...]: exactly where a fresh
        # stable owner-sort of [kept..., adds...] lands them; segment-
        # wise so the compaction temp stays cache-resident
        out = np.empty(e_new, dtype)
        for w_ in range(M):
            o, kk = new_off[w_], kept_cnt[w_]
            sl = slice(all_off[w_], all_off[w_ + 1])
            out[o:o + kk] = vals[sl] if no_rem else vals[sl][keep[sl]]
            out[o + kk:new_off[w_ + 1]] = add[ad_off[w_]:ad_off[w_ + 1]]
        return out

    na_src = _merge(all_src, a_src32, np.int32)
    na_dst = _merge(all_dst, a_dst32, np.int32)
    na_w = _merge(all_w, a_w, np.float32)

    # ---- pair_counts: monotone upper bound on the caps -----------------
    pair_counts = pg.pair_counts.copy()
    if len(a_src):
        akey = np.unique(a_owner * np.int64(n_ids) + a_dst)
        np.add.at(pair_counts,
                  ((akey // n_ids).astype(np.int64),
                   ((akey % n_ids) // n_loc).astype(np.int64)), 1)

    if int(deg_old.max()) < tau_eff and int(deg_new.max()) < tau_eff:
        # no vertex is mirrored before or after the fold: Ch_msg IS the
        # full adjacency (exactly as in a fresh partition) and every
        # mirror field is the empty sentinel pg already carries
        mask = np.ones(e_new, bool)
        return PartitionedGraph(
            n=pg.n, M=M, n_loc=n_loc, tau=tau_eff, perm=perm,
            inv_perm=pg.inv_perm,
            eg_src=na_src, eg_dst=na_dst, eg_mask=mask, eg_w=na_w,
            all_src=na_src, all_dst=na_dst, all_mask=mask, all_w=na_w,
            mir_ids=pg.mir_ids, mir_slot_of=pg.mir_slot_of,
            mir_nworkers=pg.mir_nworkers, mir_esrc=pg.mir_esrc,
            mir_edst=pg.mir_edst, mir_emask=pg.mir_emask,
            mir_ew=pg.mir_ew,
            deg=jnp.asarray(deg_new.astype(np.int32).reshape(M, n_loc)),
            vmask=pg.vmask,
            layout="csr", eg_off=new_off, all_off=new_off,
            mir_eoff=pg.mir_eoff,
            balance=pg.balance, split_factor=pg.split_factor, M_phys=M,
            pair_counts=pair_counts, hosts=pg.hosts)

    mirrored_old = deg_old >= tau_eff
    mirrored_new = deg_new >= tau_eff
    flip_up = mirrored_new & ~mirrored_old

    # ---- Ch_msg: recompact from the merged adjacency -------------------
    lo_e = ~mirrored_new[na_src]
    eg_off_n = np.concatenate(
        [[0], np.cumsum(np.bincount((na_src // n_loc)[lo_e],
                                    minlength=M))]).astype(np.int64)

    # ---- mirror csr: merge kept (already sorted) with the pool ---------
    mir_ids_old = np.asarray(pg.mir_ids, np.int64)
    m_esrc_old = np.asarray(pg.mir_esrc, np.int64)
    m_gsrc_old = (mir_ids_old[m_esrc_old] if len(m_esrc_old)
                  else np.zeros(0, np.int64))
    m_gdst_old = np.asarray(pg.mir_edst, np.int64)
    m_w_old = np.asarray(pg.mir_ew, np.float32)
    rem_mir = np.zeros(len(m_gsrc_old), bool)
    rem_mir[_removed(m_gsrc_old, m_gdst_old)] = True
    flip_dn_src = mirrored_old & ~mirrored_new
    keep_mir = ~rem_mir & ~flip_dn_src[m_gsrc_old]

    eg_src_old = np.asarray(pg.eg_src, np.int64)
    eg_dst_old = np.asarray(pg.eg_dst, np.int64)
    eg_w_old = np.asarray(pg.eg_w, np.float32)
    # removal membership only matters on the few flipped-up sources
    fu_idx = np.flatnonzero(flip_up[eg_src_old])
    fu_keep = np.ones(len(fu_idx), bool)
    fu_keep[_removed(eg_src_old[fu_idx], eg_dst_old[fu_idx])] = False
    up_idx = fu_idx[fu_keep]
    a_hi = mirrored_new[a_src]
    p_gsrc = np.concatenate([eg_src_old[up_idx], a_src[a_hi]])
    p_gdst = np.concatenate([eg_dst_old[up_idx], a_dst[a_hi]])
    p_w = np.concatenate([eg_w_old[up_idx], a_w[a_hi]]).astype(np.float32)
    # pool sorted by the mirror key (dst worker, src, dst); lexsort is
    # stable so old-before-add tie order (= fresh partition order) holds
    porder = np.lexsort((p_gdst, p_gsrc, p_gdst // n_loc))
    p_gsrc, p_gdst, p_w = p_gsrc[porder], p_gdst[porder], p_w[porder]

    def _mkey(s, d):
        # composite (dst_worker, src, dst) key; fits int64 while
        # M * n_ids^2 < 2^63 (n ~ 3e8 at M=64) — far beyond our scale
        return (d // n_loc) * (n_ids * n_ids) + s * n_ids + d

    kk = _mkey(m_gsrc_old[keep_mir], m_gdst_old[keep_mir])
    pk = _mkey(p_gsrc, p_gdst)
    n_k, n_p = len(kk), len(pk)
    pos_kept = (np.arange(n_k, dtype=np.int64)
                + np.searchsorted(pk, kk, side="left"))
    pos_pool = (np.arange(n_p, dtype=np.int64)
                + np.searchsorted(kk, pk, side="right"))
    m_gsrc = np.empty(n_k + n_p, np.int64)
    m_gdst = np.empty(n_k + n_p, np.int64)
    m_w = np.empty(n_k + n_p, np.float32)
    m_gsrc[pos_kept], m_gsrc[pos_pool] = m_gsrc_old[keep_mir], p_gsrc
    m_gdst[pos_kept], m_gdst[pos_pool] = m_gdst_old[keep_mir], p_gdst
    m_w[pos_kept], m_w[pos_pool] = m_w_old[keep_mir], p_w
    m_downer = m_gdst // n_loc
    hb_n = np.searchsorted(m_downer, np.arange(M + 1)).astype(np.int64)

    mir_vertex_ids = np.flatnonzero(mirrored_new)
    n_mir = max(len(mir_vertex_ids), 1)
    mir_idx = np.full(n_ids, -1, np.int64)
    mir_idx[mir_vertex_ids] = np.arange(len(mir_vertex_ids))
    mir_ids_arr = np.full(n_mir, n_ids, np.int32)
    mir_ids_arr[:len(mir_vertex_ids)] = mir_vertex_ids

    # ---- Theorem-1 mirror counts: copy untouched, recount touched ------
    touched = np.zeros(n_ids, bool)
    touched[m_gsrc_old[rem_mir]] = True
    touched[p_gsrc] = True
    nworkers = np.zeros(n_mir, np.int64)
    common = mirrored_old & mirrored_new & ~touched
    cids = np.flatnonzero(common)
    if len(cids):
        old_slot = np.asarray(pg.mir_slot_of, np.int64).reshape(-1)
        nworkers[mir_idx[cids]] = np.asarray(
            pg.mir_nworkers, np.int64)[old_slot[cids]]
    am = touched[m_gsrc]
    if am.any():
        pair = np.unique(m_gsrc[am] * np.int64(M) + m_downer[am])
        cnt = np.bincount((pair // M).astype(np.int64), minlength=n_ids)
        aff = np.flatnonzero(touched & mirrored_new)
        nworkers[mir_idx[aff]] = cnt[aff]

    return PartitionedGraph(
        n=pg.n, M=M, n_loc=n_loc, tau=tau_eff, perm=perm,
        inv_perm=pg.inv_perm,
        eg_src=na_src[lo_e], eg_dst=na_dst[lo_e],
        eg_mask=np.ones(int(lo_e.sum()), bool), eg_w=na_w[lo_e],
        all_src=na_src, all_dst=na_dst, all_mask=np.ones(e_new, bool),
        all_w=na_w,
        mir_ids=jnp.asarray(mir_ids_arr),
        mir_slot_of=jnp.asarray(mir_idx.astype(np.int32)
                                .reshape(M, n_loc)),
        mir_nworkers=jnp.asarray(nworkers),
        mir_esrc=mir_idx[m_gsrc].astype(np.int32),
        mir_edst=m_gdst.astype(np.int32),
        mir_emask=np.ones(n_k + n_p, bool),
        mir_ew=m_w,
        deg=jnp.asarray(deg_new.astype(np.int32).reshape(M, n_loc)),
        vmask=pg.vmask,
        layout="csr", eg_off=eg_off_n, all_off=new_off, mir_eoff=hb_n,
        balance=pg.balance, split_factor=pg.split_factor, M_phys=M,
        pair_counts=pair_counts, hosts=pg.hosts,
    )

"""Vocab-sharded embedding with the paper's request-respond lookup.

A vocab-sharded embedding table is the S-V access pattern of Yan et al. §6:
every token is a *requester* asking the owner shard of row ``id`` for its
value, and token frequency is Zipf-skewed, so a handful of rows are
bottleneck vertices.  Three lookup methods, worst first:

* ``gather``  — Pregel basic message passing: a plain ``take`` on the
  sharded table.  GSPMD resolves this by all-gathering the table
  (vocab x d_model bytes of collective traffic — the "blue bars").
* ``onehot``  — sender-side combining: each model rank computes
  ``onehot(ids) @ table_shard`` and the partial embeddings are psum'd;
  traffic drops from O(V.D) to O(T.D).
* ``rr``      — the request-respond channel: per shard, token ids are
  **deduplicated** (sort-based, static capacity U = min(T, V) which is an
  exact bound on distinct requests), one request per unique id is resolved
  via the onehot/psum combine, and the (U, D) *response table* is scattered
  back to tokens locally — Theorem 3's 2.min(M, l) bound with the response
  payload shrunk from T rows to U rows.

The logits projection shares the table (vocab-sharded); its softmax
reductions over the sharded vocab axis lower to scalar-sized all-reduces.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def dedup_ids(ids: jax.Array, capacity: int):
    """Sort-based fixed-capacity dedup (static shapes, jit-safe).

    ids: (T,) int32. Returns (uniq (capacity,), inv (T,)) such that
    ``uniq[inv] == ids``; unused uniq slots hold 0.  capacity must be
    >= number of distinct ids (capacity = min(T, vocab) always is).
    """
    T = ids.shape[0]
    order = jnp.argsort(ids)
    s = ids[order]
    first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    # rank of each sorted element among uniques
    rank = jnp.cumsum(first) - 1                      # (T,)
    uniq = jnp.zeros((capacity,), ids.dtype).at[rank].max(s)
    inv = jnp.zeros((T,), rank.dtype).at[order].set(rank)
    n_uniq = rank[-1] + 1
    return uniq, inv, n_uniq


def embed_lookup(table: jax.Array, ids: jax.Array, method: str = "rr",
                 rr_capacity: int = 0) -> jax.Array:
    """table: (V, D) (vocab-sharded under jit); ids: (..., ) int32.

    Written with plain ops + sharding-friendly one-hot contractions; under
    pjit the table stays vocab-sharded and only combined partial sums move.
    """
    shape = ids.shape
    flat = ids.reshape(-1)
    V, D = table.shape
    if method == "gather":
        out = jnp.take(table, flat, axis=0)
    elif method == "onehot":
        oh = jax.nn.one_hot(flat, V, dtype=table.dtype)
        out = jnp.einsum("tv,vd->td", oh, table)
    elif method == "rr":
        cap = rr_capacity or min(flat.shape[0], V)
        uniq, inv, _ = dedup_ids(flat, cap)
        oh = jax.nn.one_hot(uniq, V, dtype=table.dtype)
        resp = jnp.einsum("uv,vd->ud", oh, table)  # response table (U, D)
        out = jnp.take(resp, inv, axis=0)          # local scatter to requesters
    else:
        raise ValueError(method)
    return out.reshape(*shape, D)


def embed_lookup_sharded(table: jax.Array, ids: jax.Array, mesh,
                         dp_axes: tuple, mp_axis: str = "model"
                         ) -> jax.Array:
    """Paper-faithful request-respond lookup under a mesh: each data-parallel
    *worker* dedups its own token ids (the per-worker request set of §6),
    resolves one request per distinct id against the vocab-sharded table
    (one-hot partial + psum over the model axis = the response exchange),
    and scatters the (U, D) response table back to its tokens locally.

    Crucially the dedup is per shard, so batch sharding survives the
    embedding (a global argsort would force GSPMD to replicate the batch —
    the defect this replaced; see EXPERIMENTS.md §Dry-run)."""
    B, S = ids.shape
    V, D = table.shape
    mp = mesh.shape[mp_axis]
    dp_size = math.prod(mesh.shape[a] for a in dp_axes)
    if B % dp_size or V % mp:
        # fall back: local dedup semantics with a sharding constraint
        from jax.sharding import NamedSharding
        out = embed_lookup(table, ids, method="rr")
        return lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(None, None, None)))
    v_loc = V // mp

    def body(ids_loc, table_loc):
        flat = ids_loc.reshape(-1)                    # (T_loc,)
        cap = min(flat.shape[0], V)
        uniq, inv, _ = dedup_ids(flat, cap)           # per-WORKER request set
        vstart = lax.axis_index(mp_axis) * v_loc
        cols = vstart + jnp.arange(v_loc)
        oh = (uniq[:, None] == cols[None, :]).astype(table_loc.dtype)
        part = jnp.einsum("uv,vd->ud", oh, table_loc)  # local response rows
        resp = lax.psum(part, mp_axis)                 # response exchange
        out = jnp.take(resp, inv, axis=0)              # local scatter
        return out.reshape(ids_loc.shape[0], S, D)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None), P(mp_axis, None)),
        out_specs=P(dp_axes, None, None),
        check_vma=False,
    )(ids, table)


def logits_matmul(h: jax.Array, table: jax.Array) -> jax.Array:
    """h: (B, S, D) -> logits (B, S, V), vocab axis stays sharded."""
    return jnp.einsum("bsd,vd->bsv", h, table,
                      preferred_element_type=jnp.float32)


def softmax_xent(logits: jax.Array, labels: jax.Array, mask: jax.Array):
    """Cross-entropy over (possibly vocab-sharded) logits.

    logits: (B, S, V) fp32; labels: (B, S) int32; mask: (B, S) {0,1}.
    The max/sum reductions over V lower to tiny all-reduces when V is
    sharded; the label pick uses a one-hot contraction (shard-friendly).
    """
    V = logits.shape[-1]
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    oh = jax.nn.one_hot(labels, V, dtype=logits.dtype)
    picked = jnp.sum(logits * oh, axis=-1)
    nll = (lse - picked) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Graph-node embeddings on the BSP engine (vector Ch_req payloads)
# ---------------------------------------------------------------------------

def node_embedding_init(pg, feat_dim: int, seed: int = 0,
                        scale: float | None = None,
                        dtype=jnp.float32) -> jax.Array:
    """Worker-sharded node-embedding table for a partitioned graph.

    Returns a ``(M, n_loc, feat_dim)`` array — the engine's row-state
    shape with ONE trailing feature axis, i.e. exactly the vector-payload
    convention every channel accepts.  Rows are N(0, scale) for real
    vertices (``scale`` defaults to ``feat_dim**-0.5``) and zero for the
    layout's padding slots, so padded rows contribute nothing to joins.
    The init is a function of the ORIGINAL vertex id (placed through
    ``pg.perm``): two partitions of the same graph start from the same
    embedding for every vertex, which is what the sharded-vs-unsharded
    gradient-parity tests rely on."""
    import numpy as np
    if scale is None:
        scale = float(feat_dim) ** -0.5
    rng = np.random.RandomState(seed)
    rows = rng.randn(pg.n, feat_dim).astype(np.float32) * scale
    tab = np.zeros((pg.n_pad, feat_dim), np.float32)
    tab[np.asarray(pg.perm)] = rows
    return jnp.asarray(tab, dtype).reshape(pg.M, pg.n_loc, feat_dim)


def node_embedding_fetch(g, table: jax.Array, ids: jax.Array,
                         mask: jax.Array):
    """Sparse embedding lookup over the request-respond channel.

    ``table`` is the sharded ``(rows, n_loc, F)`` node table; ``ids``
    ``(rows, R)`` global (padded) vertex ids each worker wants rows for.
    This is the S-V access pattern of §6 with a VECTOR payload: requests
    are deduplicated per worker, the owner responds once per distinct id
    with the full ``(F,)`` block, and the response table is scattered back
    locally — returns ``((rows, R, F) values, stats)``.  Works unsharded
    (PartitionedGraph) and inside ``shard_map`` (ShardedGraph), where the
    respond leg lowers to the routed (lanes, F) exchange."""
    from repro.core import channels
    return channels.gather(g, table, ids, mask)

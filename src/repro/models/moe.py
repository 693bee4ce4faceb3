"""Mixture-of-Experts FFN with the paper's message-reduction techniques.

Mapping of Yan et al.'s ideas onto expert parallelism:

* **Sender-side message combining** (paper §4/§5): tokens headed to the same
  expert are packed into one contiguous per-(sender, expert) buffer *before*
  the ``all_to_all`` — one batched message per destination rank instead of
  one message per token, exactly the Pregel+ combined channel.
* **Mirroring** (paper §5, Thm 1/2 analog): the ``n_mirrored_experts``
  hottest experts are replicated on every EP rank; tokens routed to them are
  served locally and never enter the all_to_all, bounding the fan-in of a
  hot expert the same way a mirror bounds a high-degree vertex's fan-out.
  ``repro.core.cost_model.moe_mirror_threshold`` gives the Thm-2-style
  arbitration between replication (weight memory) and message savings.

Dispatch is capacity-bounded (static shapes): C tokens per (sender rank,
expert); overflow tokens are dropped with zero contribution — the standard
Switch/GShard semantics.  Two implementations with identical math:

* ``moe_ffn_ref``    — single-buffer reference (runs anywhere, oracle).
* ``moe_ffn_ep``     — shard_map expert-parallel version used under a mesh.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig


@dataclasses.dataclass(frozen=True)
class MoEContext:
    """How the MoE layer is distributed. ep_axis is the mesh axis that shards
    experts; None means run the local reference path."""
    mesh: Optional[object] = None
    ep_axis: str = "model"
    dp_axes: tuple = ("data",)


def router_probs(x: jax.Array, w_router: jax.Array, top_k: int):
    """Return (gates, expert_idx): top-k router with renormalized softmax.
    x: (T, D), w_router: (D, E) -> gates (T, k), idx (T, k)."""
    logits = jnp.einsum("td,de->te", x, w_router,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates.astype(x.dtype), idx, probs


def load_balance_loss(probs: jax.Array, idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch-transformer auxiliary loss: E * <f_e> . <p_e>."""
    f = jnp.mean(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum(1), axis=0)
    p = jnp.mean(probs.astype(jnp.float32), axis=0)
    return n_experts * jnp.sum(f * p)


def _expert_mlp(xe: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array) -> jax.Array:
    """xe: (C, D) tokens for one expert."""
    g = jnp.einsum("cd,df->cf", xe, wg)
    u = jnp.einsum("cd,df->cf", xe, wu)
    return jnp.einsum("cf,fd->cd", jax.nn.silu(g) * u, wd)


def _pack(x, idx, gates, n_experts, cap, mirrored_mask):
    """Sender-side combining: scatter local tokens into a per-expert buffer.

    x: (T, D); idx/gates: (T, k). Returns:
      buf       (E, C, D) combined send buffer
      buf_gate  (E, C)    gate weight per slot
      buf_tok   (E, C)    source token index (for the return combine)
    Tokens whose expert is mirrored are EXCLUDED (mirrored_mask (E,) bool) —
    they never become network messages.
    """
    T, D = x.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)                      # (T*k,)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    send = ~mirrored_mask[flat_e]
    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32) * send[:, None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot     # exclusive prefix count
    slot = (pos * onehot).sum(-1)                 # (T*k,)
    keep = send & (slot < cap)
    dest = jnp.where(keep, flat_e * cap + slot, n_experts * cap)  # overflow -> dropped row
    buf = jnp.zeros((n_experts * cap + 1, D), x.dtype).at[dest].add(x[flat_t])
    buf_gate = jnp.zeros((n_experts * cap + 1,), gates.dtype).at[dest].add(flat_g)
    buf_tok = jnp.full((n_experts * cap + 1,), -1, jnp.int32).at[dest].max(flat_t)
    return (buf[:-1].reshape(n_experts, cap, D),
            buf_gate[:-1].reshape(n_experts, cap),
            buf_tok[:-1].reshape(n_experts, cap))


def _unpack(y_buf, buf_gate, buf_tok, T, D):
    """Combine expert outputs back per source token (receiver-side combine)."""
    flat_y = y_buf.reshape(-1, D) * buf_gate.reshape(-1)[:, None]
    flat_t = buf_tok.reshape(-1)
    valid = flat_t >= 0
    tgt = jnp.where(valid, flat_t, T)
    out = jnp.zeros((T + 1, D), y_buf.dtype).at[tgt].add(flat_y)
    return out[:-1]


def moe_ffn_ref(x: jax.Array, w: dict, cfg: MoEConfig) -> tuple:
    """Reference single-worker dispatch. x: (T, D). w holds
    router (D,E), w_gate/w_up (E,D,F), w_down (E,F,D)."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    cap = max(1, int(cfg.capacity_factor * T * k / E))
    gates, idx, probs = router_probs(x, w["router"], k)
    mirrored = jnp.zeros((E,), bool)
    buf, bg, bt = _pack(x, idx, gates, E, cap, mirrored)
    y_buf = jax.vmap(_expert_mlp)(buf, w["w_gate"], w["w_up"], w["w_down"])
    y = _unpack(y_buf, bg, bt, T, D)
    aux = load_balance_loss(probs, idx, E)
    return y, aux


def moe_ffn_ep(x: jax.Array, w: dict, cfg: MoEConfig, ctx: MoEContext) -> tuple:
    """Expert-parallel dispatch under shard_map.

    Token activations arrive sharded over dp axes and the ep axis (fully
    token-sharded); experts are sharded over ``ep_axis``. Per EP rank:
      route -> pack per-(rank,expert) combined buffers -> all_to_all(ep)
      -> local experts -> all_to_all back -> combine.
    Mirrored experts short-circuit the network entirely.
    """
    mesh = ctx.mesh
    ep = ctx.ep_axis
    E, k = cfg.n_experts, cfg.top_k
    ep_size = mesh.shape[ep]
    e_loc = E // ep_size
    n_m = min(cfg.n_mirrored_experts, E)

    def body(xs, router, wg, wu, wd, wgm, wum, wdm):
        # xs: (T_loc, D) local tokens; wg/...: (e_loc, D, F) local experts;
        # w*m: (n_m, D, F) mirrored (replicated) experts.
        T_loc, D = xs.shape
        cap = max(1, int(cfg.capacity_factor * T_loc * k / E))
        gates, idx, probs = router_probs(xs, router, k)
        mirrored = jnp.arange(E) < n_m  # hottest-first layout (see cost_model)
        buf, bg, bt = _pack(xs, idx, gates, E, cap, mirrored)
        # ---- network path: one combined message per (dst rank, expert) ----
        buf = buf.reshape(ep_size, e_loc, cap, D)
        recv = lax.all_to_all(buf, ep, split_axis=0, concat_axis=0, tiled=False)
        # recv: (ep_size_src, e_loc, cap, D) -> per local expert, all senders
        recv = recv.transpose(1, 0, 2, 3).reshape(e_loc, ep_size * cap, D)
        y = jax.vmap(_expert_mlp)(recv, wg, wu, wd)
        y = y.reshape(e_loc, ep_size, cap, D).transpose(1, 0, 2, 3)
        y = lax.all_to_all(y, ep, split_axis=0, concat_axis=0, tiled=False)
        out = _unpack(y.reshape(E, cap, D), bg, bt, T_loc, D)
        # ---- mirrored path: local compute, zero messages ----
        for j in range(n_m):
            g = ((idx == j) * gates).sum(-1)
            out = out + _expert_mlp(xs, wgm[j], wum[j], wdm[j]) * g[:, None]
        aux = lax.pmean(load_balance_loss(probs, idx, E), (*dp, ep))
        return out, aux

    dp = ctx.dp_axes
    tok_spec = P((*dp, ep), None)
    exp_spec = P(ep, None, None)
    rep = P(None, None, None)
    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, P(None, None), exp_spec, exp_spec, exp_spec,
                  rep, rep, rep),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(x, w["router"], w["w_gate"], w["w_up"], w["w_down"],
      w["w_gate_m"], w["w_up_m"], w["w_down_m"])
    return y, aux

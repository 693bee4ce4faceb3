"""Pallas TPU flash attention (prefill/train path).

Canonical online-softmax tiling: grid (batch*q_heads, n_q_blocks,
n_kv_blocks), sequential over the kv axis with the running max/denominator
and the output accumulator in VMEM scratch (TPU grids iterate the last axis
innermost, so scratch persists across the kv sweep for a fixed (bh, q)).

GQA is handled in the BlockSpec index map (kv head = q head // n_rep), so
repeated K/V are never materialized in HBM — one of the memory-term
optimizations measured in EXPERIMENTS.md §Perf.

Supports causal masking and sliding windows; VMEM per step =
Bq*d + 2*Bk*d + Bq*Bk floats (default 512x512 blocks, d<=256: ~1.5MB).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG = -2.0 ** 30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            n_kv: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (Bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (Bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qi = pl.program_id(1)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), bool)
    if causal:
        ok &= q_pos >= k_pos
    if window > 0:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         bq: int = 512, bk: int = 512,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q: (BH, Sq, d); k/v: (BKV, Sk, d) with BH % BKV == 0 (GQA)."""
    interpret = resolve_interpret(interpret)
    BH, Sq, d = q.shape
    BKV, Sk, _ = k.shape
    n_rep = BH // BKV
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    grid = (BH, Sq // bq, Sk // bk)
    scale = d ** -0.5
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq, bk=bk, n_kv=Sk // bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, n_rep=n_rep: (b // n_rep, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, n_rep=n_rep: (b // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),     # running max
            pltpu.VMEM((bq,), jnp.float32),     # running denominator
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)

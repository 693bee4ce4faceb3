"""Pallas TPU kernel for the Mamba-2 SSD chunk scan.

Grid (B*H, n_chunks); the running inter-chunk state (P x N) lives in VMEM
scratch and persists across the sequential chunk axis — the HBM-resident
state tensor of a naive implementation never exists.  Per chunk, the
intra-chunk 1-semiseparable term runs as three small MXU matmuls; the state
update is one more.  VMEM per step: Q*(P+2N) inputs + Q*Q decay + P*N state
(Q=128, P=64, N=128: ~270KB f32).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, y_ref, state_ref, *,
            q: int, p: int, n: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (Q,)
    A = A_ref[0].astype(jnp.float32)          # scalar (per head)
    Bm = B_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = C_ref[0].astype(jnp.float32)         # (Q, N)

    dtA = dt * A                               # (Q,) <= 0
    cum = jnp.cumsum(dtA)                      # (Q,)
    xdt = x * dt[:, None]

    # intra-chunk: L[i,j] = exp(cum[i]-cum[j]) for i>=j
    seg = cum[:, None] - cum[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(row >= col, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the incoming state
    state = state_ref[...]                     # (P, N)
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: S <- exp(cum[-1]) * S + sum_j decay_to_end[j] xdt_j B_j^T
    decay_end = jnp.exp(cum[-1] - cum)         # (Q,)
    contrib = jax.lax.dot_general(xdt * decay_end[:, None], Bm,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(cum[-1]) * state + contrib
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan_bh(x, dt, A, B, C, *, chunk: int,
                interpret: Optional[bool] = None):
    """x: (BH, S, P); dt: (BH, S); A: (BH,); B, C: (BH, S, N).
    Returns y: (BH, S, P)."""
    interpret = resolve_interpret(interpret)
    BH, S, P = x.shape
    N = B.shape[-1]
    assert S % chunk == 0
    grid = (BH, S // chunk)
    return pl.pallas_call(
        functools.partial(_kernel, q=chunk, p=P, n=N),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C)

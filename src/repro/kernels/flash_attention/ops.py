"""jit'd wrapper: (B, S, H, hd) model layout <-> kernel layout."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.flash_attention.ref import flash_attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                   "use_kernel", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, bq=512, bk=512,
                    use_kernel=True, interpret=None):
    """q: (B, Sq, H, hd); k/v: (B, Sk, K, hd).  Returns (B, Sq, H, hd).

    Row b*H + h of the flattened q maps to kv row b*K + h // (H/K):
    exactly the kernel's ``b // n_rep`` BlockSpec index map, so GQA repeats
    are never materialized.
    """
    B, Sq, H, hd = q.shape
    _, Sk, K, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    if use_kernel:
        o = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                                 bq=bq, bk=bk, interpret=interpret)
    else:
        o = flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    return o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)

"""BSP superstep runtime: jit-compiled while-loop with halt voting,
aggregators, and per-superstep message accounting.

A *program* is a function ``step(state, superstep) -> (state, halted, stats)``
where ``state`` is any pytree of (M, ...) arrays, ``halted`` a scalar bool
(the paper's "all vertices voted to halt & no pending messages"), and
``stats`` a flat dict of scalars / (M,) arrays.  The runtime accumulates
stats totals and an optional per-superstep history, and supports
checkpoint/restore of the loop carry (fault tolerance: the whole BSP state
is a pytree).

``run`` also executes unchanged *inside* a ``shard_map`` region (the
sharded executor in ``core/exec.py``): the step then computes ``halted``
and the stats with cross-device collectives so the carried halt flag and
accumulated totals are replicated across the mesh.

Stats contract: every ``per_worker_*`` entry is an (M,) array over the
*logical* workers.  Split partitions (``balance="split"``) run their
channels per physical shard, but the channel layer folds shard counts back
through ``pg.phys_log`` before the stats reach this loop — accumulation
here never needs to know how many physical shards a worker was split into,
and histories/totals stay comparable across balance modes and device
counts.

Overflow contract: per-superstep counts are int32 (a single superstep of
even a billion-edge graph fits), but multi-superstep TOTALS of the
nightly-scale runs approach 2^31.  Totals are therefore carried as
(hi, lo) int32 limb pairs inside the jitted loop — ``lo`` wraps mod 2^32
with an unsigned-compare carry into ``hi`` — and folded into Python
ints / numpy int64 on the host once the loop finishes (``jax_enable_x64``
stays off).  Integer histories stay int32 per superstep.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans

_SIGN = -2 ** 31  # int32 sign bit: xor flips signed compare into unsigned


def _is_int(leaf) -> bool:
    return jnp.issubdtype(leaf.dtype, jnp.integer)


def _ult(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Elementwise unsigned a < b on int32 (two's-complement trick)."""
    s = jnp.int32(_SIGN)
    return jnp.bitwise_xor(a, s) < jnp.bitwise_xor(b, s)


def acc_init(stats_leaves):
    """Zero accumulator: (hi, lo) int32 pairs for integer leaves, the
    leaf's own dtype for floats."""
    return [
        (jnp.zeros(s.shape, jnp.int32), jnp.zeros(s.shape, jnp.int32))
        if _is_int(s) else jnp.zeros(s.shape, s.dtype)
        for s in stats_leaves
    ]


def acc_add(acc, stats_leaves):
    """Add one superstep's (non-negative int32) counts into the limbs."""
    out = []
    for a, s in zip(acc, stats_leaves):
        if isinstance(a, tuple):
            hi, lo = a
            new = lo + s.astype(jnp.int32)          # wraps mod 2^32
            carry = _ult(new, lo).astype(jnp.int32)  # s >= 0: wrap <=> ult
            out.append((hi + carry, new))
        else:
            out.append(a + s)
    return out


def finalize_totals(acc, treedef):
    """HOST-side fold of the limb pairs into exact numpy int64 (scalars
    become Python ints) — never call on tracers."""
    out = []
    for a in acc:
        if isinstance(a, tuple):
            hi = np.asarray(a[0]).astype(np.int64)
            lo = np.asarray(a[1]).astype(np.int64) & 0xFFFFFFFF
            tot = (hi << 32) + lo
            out.append(int(tot) if tot.ndim == 0 else tot)
        else:
            out.append(np.asarray(a))
    return jax.tree.unflatten(treedef, out)


def run(step: Callable, state, max_supersteps: int,
        record_history: bool = False, raw_totals: bool = False,
        pipeline: bool = False
        ) -> Tuple[object, Dict, jnp.ndarray, Optional[Dict]]:
    """Run ``step`` until halt or max_supersteps.

    Always returns the 4-tuple ``(final_state, stats_totals, n_supersteps,
    history)`` — ``history`` is the per-superstep stats pytree (leading
    ``max_supersteps`` axis) when ``record_history=True`` and ``None``
    otherwise, so callers never have to special-case the arity.

    ``raw_totals=False`` (the default) folds the carried (hi, lo) limb
    pairs into exact host-side Python ints / numpy int64.  The sharded
    executor runs this loop *inside* ``shard_map`` where no host exists;
    it passes ``raw_totals=True`` to get the raw limb list back (fold it
    with ``finalize_totals`` + the treedef of the per-superstep stats
    once outside the jit boundary).

    ``pipeline=True`` double-buffers the (hi, lo) limb fold: superstep
    ``i``'s counts are carried one iteration and folded while superstep
    ``i+1``'s exchange is in flight (the last pending superstep folds in
    an epilogue after the loop).  Limb addition is associative and the
    initial pending slot is all-zero, so totals are bit-identical to the
    unpipelined fold — the flag only moves the add off the superstep's
    critical path.
    """
    _, _, stats0 = jax.eval_shape(step, state, jnp.zeros((), jnp.int32))
    leaves0, treedef = jax.tree.flatten(stats0)
    zero_acc = acc_init(leaves0)
    zero_pending = [jnp.zeros(s.shape, s.dtype) for s in leaves0]
    history0 = None
    if record_history:
        history0 = jax.tree.map(
            lambda s: jnp.zeros((max_supersteps,) + s.shape, s.dtype), stats0)

    def cond(carry):
        _, halted, i, _, _, _ = carry
        return (~halted) & (i < max_supersteps)

    def body(carry):
        st, _, i, acc, hist, pending = carry
        with spans.scope(spans.SUPERSTEP):
            st, halted, stats = step(st, i)
        leaves = jax.tree.leaves(stats)
        with spans.scope(spans.STATS):
            if pipeline:
                # fold the PREVIOUS superstep's counts while this
                # superstep's exchange is still in flight; stash this one
                # for the next iteration (or the epilogue)
                acc = acc_add(acc, pending)
                pending = leaves
            else:
                acc = acc_add(acc, leaves)
        if record_history:
            hist = jax.tree.map(lambda h, s: h.at[i].set(s), hist, stats)
        return st, halted, i + 1, acc, hist, pending

    carry = (state, jnp.zeros((), bool), jnp.zeros((), jnp.int32),
             zero_acc, history0, zero_pending)
    st, _, n, acc, hist, pending = jax.lax.while_loop(cond, body, carry)
    if pipeline:
        with spans.scope(spans.STATS):
            acc = acc_add(acc, pending)      # the last deferred superstep
    if raw_totals:
        return st, acc, n, hist
    return st, finalize_totals(acc, treedef), n, hist


def aggregate_or(x: jnp.ndarray) -> jnp.ndarray:
    """Aggregator: global OR (e.g. 'did any vertex update?')."""
    return jnp.any(x)


def aggregate_sum(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(x)

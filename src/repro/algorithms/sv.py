"""Shiloach-Vishkin connected components (paper §3.4) — the request-respond
showcase: every vertex u reads D[D[u]] from the owner of D[u], and towards
the end ALL vertices of a component request the same root (the Fig. 2
bottleneck).  Min-hooking variant (hook larger roots onto smaller labels),
which converges to the minimum id of each component in O(log n) rounds.

Message accounting: every pointer read is a request-respond exchange
(msgs_rr vs msgs_basic = the with/without-Ch_req comparison of Fig. 13);
hooking writes go through the combined scatter channel.

Labels are combined in int32 end to end (identity = iinfo sentinel, no
float32 round-trip): float32 cannot represent ids >= 2^24, so the old cast
merged distinct components on large graphs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.api import EngineConfig, RunResult, warn_legacy
from repro.core import bsp
from repro.core import exec as exec_mod
from repro.core import spans
from repro.core.channels import broadcast, gather, scatter_state
from repro.core.plan import identity_of
from repro.graph.structs import PartitionedGraph


def _acc(stats, s, workers):
    """Accumulate a channel stats dict into uniform rr/basic counters."""
    with spans.scope(spans.STATS):
        rr = s.get("msgs_rr", s.get("msgs_combined", 0))
        stats["msgs_rr"] = stats.get("msgs_rr", 0) + rr
        stats["msgs_basic"] = stats.get("msgs_basic", 0) + s["msgs_basic"]
        pw_rr = s.get("per_worker_rr", s.get("per_worker_combined"))
        stats["per_worker_rr"] = stats.get(
            "per_worker_rr", jnp.zeros(workers, jnp.int32)) + pw_rr
        stats["per_worker_basic"] = (stats.get("per_worker_basic",
                                               jnp.zeros(workers, jnp.int32))
                                     + s["per_worker_basic"])
    return stats


def run(pg: PartitionedGraph, config: EngineConfig | None = None, *,
        max_supersteps: int = 64) -> RunResult:
    """Shiloach-Vishkin under an EngineConfig.  ``state`` is the
    (M, n_loc) int32 label array (min id of each CC).  Pointer reads are
    request-respond exchanges, so ``use_mirroring`` does not apply."""
    cfg = config or EngineConfig()
    imax = identity_of("min", jnp.int32)
    backend = cfg.backend

    def make_step(g):
        M = g.M

        def step(state, i):
            D = state
            stats: dict = {}

            # D[D[u]]  — THE skewed pointer read (request-respond)
            DD, s = gather(g, D, D, g.vmask)
            stats = _acc(stats, s, M)
            parent_is_root = DD == D

            # cand[u] = min over neighbors v of D[v] (push D, min combiner,
            # in the id dtype — int32 identity, no float32 round-trip)
            cand_i, s = broadcast(g, D, g.vmask, op="min",
                                  use_mirroring=False, backend=backend)
            stats = _acc(stats, s, M)
            has_nbr = cand_i != imax
            cand = jnp.where(has_nbr, cand_i, 2 ** 30)

            # (1) tree hooking: roots get hooked onto smaller neighbor-parents
            hook_mask = g.vmask & parent_is_root & has_nbr & (cand < D)
            D1, s = scatter_state(g, D, D, cand, hook_mask, "min",
                                  backend=backend)
            stats = _acc(stats, s, M)

            # star detection on the hooked forest
            DD1, s = gather(g, D1, D1, g.vmask)
            stats = _acc(stats, s, M)
            star = (DD1 == D1).astype(jnp.int32)
            deep = g.vmask & (DD1 != D1)
            star, s = scatter_state(g, star, DD1, jnp.zeros_like(star),
                                    deep, "min", backend=backend)
            stats = _acc(stats, s, M)
            star_of_parent, s = gather(g, star, D1, g.vmask)
            stats = _acc(stats, s, M)
            in_star = g.vmask & (star_of_parent > 0)

            # (2) star hooking
            hook2 = in_star & has_nbr & (cand < D1)
            D2, s = scatter_state(g, D1, D1, cand, hook2, "min",
                                  backend=backend)
            stats = _acc(stats, s, M)

            # (3) shortcutting: D[u] = D[D[u]]
            DD2, s = gather(g, D2, D2, g.vmask)
            stats = _acc(stats, s, M)
            D3 = jnp.where(g.vmask, jnp.minimum(D2, DD2), D)

            halted = (g.gall(D3 == D) & ~g.gany(hook_mask)
                      & ~g.gany(hook2))
            return D3, halted, stats
        return step

    D0 = pg.local_ids().astype(jnp.int32)
    if cfg.devices is None:
        D, stats, n, _ = bsp.run(jax.jit(make_step(pg)), D0, max_supersteps,
                                 pipeline=cfg.pipeline)
    else:
        D, stats, n, _ = exec_mod.run_sharded(
            pg, make_step, D0, max_supersteps, devices=cfg.devices,
            plan_kinds=exec_mod.broadcast_plan_kinds(
                backend, use_mirroring=False),
            pipeline=cfg.pipeline)
    return RunResult(state=D, stats=stats, n_supersteps=n)


def sv(pg: PartitionedGraph, max_supersteps: int = 64,
       backend: str = "dense", devices: int | None = None,
       pipeline: bool = False):
    """Deprecated positional-tuple wrapper: returns (labels, stats,
    rounds).  Use ``Engine.run("sv", ...)``."""
    warn_legacy("sv()", 'Engine.run("sv", ...)')
    res = run(pg, EngineConfig(backend=backend, devices=devices,
                               pipeline=pipeline),
              max_supersteps=max_supersteps)
    return res.state, res.stats, res.n_supersteps

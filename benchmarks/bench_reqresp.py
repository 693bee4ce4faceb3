"""Paper Fig. 13: request-respond vs basic Pregel on attribute broadcast,
S-V, and MSF (message counts are exact; both counts come from one run since
Ch_req returns identical values, only the message accounting differs)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import paper_graphs, row, timed
from repro.algorithms.attr_bcast import attribute_broadcast
from repro.algorithms.msf import msf
from repro.algorithms.sv import sv
from repro.graph.structs import partition
from repro.core.cost_model import straggler_report

M = 16


def run(scale=20_000):
    print("# Fig13: name,us_per_call,rr|basic|reduction|balance")
    graphs = paper_graphs(scale)

    for gname in ["webuk_like", "btc_like", "twitter_like"]:
        g = graphs[gname].symmetrized()
        pg = partition(g, M, tau=None, seed=0)
        attr = jnp.arange(pg.n_pad, dtype=jnp.float32).reshape(pg.M, pg.n_loc)
        (out, stats), secs = timed(attribute_broadcast, pg, attr)
        rr, basic = int(stats["msgs_rr"]), int(stats["msgs_basic"])
        row(f"fig13.attr_bcast.{gname}", secs,
            f"rr={rr};basic={basic};x={basic / max(rr, 1):.2f}")

    for gname in ["usa_like", "btc_like"]:
        g = graphs[gname].symmetrized()
        pg = partition(g, M, tau=None, seed=0)
        (labels, stats, n), secs = timed(sv, pg)
        rr, basic = int(stats["msgs_rr"]), int(stats["msgs_basic"])
        bal_rr = straggler_report(np.asarray(stats["per_worker_rr"]))
        bal_b = straggler_report(np.asarray(stats["per_worker_basic"]))
        row(f"fig13.sv.{gname}", secs,
            f"rr={rr};basic={basic};x={basic / max(rr, 1):.2f}"
            f";maxmean_rr={bal_rr['max_over_mean']:.2f}"
            f";maxmean_basic={bal_b['max_over_mean']:.2f};rounds={int(n)}")

    for gname in ["usa_like", "btc_like"]:
        g = graphs[gname]
        if g.weight is None:
            rng = np.random.RandomState(1)
            g.weight = rng.rand(g.m).astype(np.float32) + 0.01
        g = g.symmetrized()
        pg = partition(g, M, tau=None, seed=0)
        (res, stats, n), secs = timed(msf, pg)
        rr, basic = int(stats["msgs_rr"]), int(stats["msgs_basic"])
        row(f"fig13.msf.{gname}", secs,
            f"rr={rr};basic={basic};x={basic / max(rr, 1):.2f}"
            f";w={float(res[1]):.1f};rounds={int(n)}")
    return True


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()

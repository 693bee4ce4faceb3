"""Persistent graph-service demo — the acceptance workload.

    PYTHONPATH=src python -m repro.launch.serve_graph \
        --n 200000 --devices 1 --workers 32

Boots a :class:`repro.core.service.GraphService` holding a resident
partitioned + sharded powerlaw graph, then:

1. warms the bucket executors (each traces exactly once);
2. answers a 64-query mixed batch (landmark SSSP + personalized
   PageRank + ego-component lookups) from ONE compiled executor —
   the service's trace counter is asserted flat across the batch;
3. streams a 1%-edge-churn :class:`~repro.graph.structs.EdgeDelta`,
   folded between supersteps by ``fold_delta`` (no re-partition, no
   re-trace — asserted), and
4. checks post-fold answers against a fresh full ``partition()`` of the
   mutated edge list (SSSP + PPR to tolerance, ego exactly).

Args are parsed before jax is imported so ``--devices`` can force host
devices via XLA_FLAGS — keep the repro imports lazy.
"""
from __future__ import annotations

import argparse
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--avg-deg", type=float, default=8.0)
    ap.add_argument("--workers", type=int, default=32)
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the resident graph over this many "
                         "devices (on CPU, forced host devices)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=64,
                    help="queries per mixed batch")
    ap.add_argument("--buckets", type=int, nargs="+", default=[4, 16, 64],
                    help="query-batch padding buckets (one executor each)")
    ap.add_argument("--churn", type=float, default=0.01,
                    help="fraction of edges removed AND added by the "
                         "streamed mutation")
    ap.add_argument("--ppr-iters", type=int, default=20)
    ap.add_argument("--skip-parity", action="store_true",
                    help="skip the fresh-full-partition cross-check "
                         "(for timing-only runs)")
    return ap


def _mixed_batch(n, size, seed):
    import numpy as np
    from repro.core.service import Query
    rng = np.random.RandomState(seed)
    kinds = (["sssp"] * (size // 3) + ["ppr"] * (size // 3)
             + ["ego"] * (size - 2 * (size // 3)))
    return [Query(k, int(s)) for k, s in zip(kinds, rng.randint(0, n,
                                                                size=size))]


def _churn_delta(g, frac, seed):
    import numpy as np
    from repro.graph.structs import EdgeDelta
    rng = np.random.RandomState(seed + 1)
    half = g.m // 2            # symmetrized: mutate lo<hi halves, mirror
    k = max(int(half * frac), 1)
    ridx = rng.choice(half, size=k, replace=False)
    lo = np.minimum(g.src, g.dst)
    hi = np.maximum(g.src, g.dst)
    key = np.unique(lo.astype(np.int64) * g.n + hi)
    rs, rd = key[ridx] // g.n, key[ridx] % g.n
    a_s = rng.randint(0, g.n, size=k)
    a_d = rng.randint(0, g.n, size=k)
    keep = a_s != a_d
    a_w = rng.rand(int(keep.sum())).astype(np.float32) + 0.01
    return EdgeDelta(add_src=a_s[keep], add_dst=a_d[keep], add_w=a_w,
                     rem_src=rs, rem_dst=rd).symmetrized()


def main():
    args = build_parser().parse_args()
    if args.devices > 1:
        from repro.launch.xla_flags import force_host_devices
        force_host_devices(args.devices)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import numpy as np
    from repro.api import Engine, EngineConfig
    from repro.core.service import GraphClient, GraphService, Query
    from repro.graph import generators
    from repro.graph.structs import canonical_labels, partition

    g = generators.powerlaw(args.n, avg_deg=args.avg_deg, seed=args.seed,
                            weighted=True).symmetrized()
    cfg = EngineConfig(layout="csr", balance="edges", devices=args.devices)
    t0 = time.time()
    svc = GraphService(g, M=args.workers, config=cfg,
                       buckets=args.buckets, ppr_iters=args.ppr_iters,
                       seed=args.seed)
    client = GraphClient(svc)
    print(f"[serve-graph] resident graph n={g.n} m={g.m} "
          f"M={args.workers} tau={svc.pg.tau} devices={args.devices} "
          f"partitioned in {time.time() - t0:.2f}s")

    t0 = time.time()
    svc.warmup()
    warm_traces = svc.traces
    print(f"[serve-graph] warmup: {warm_traces} traces "
          f"(buckets {svc.buckets} + components) in "
          f"{time.time() - t0:.2f}s")

    # -- 2. the 64-query mixed batch, one executor, zero re-traces -------
    batch = _mixed_batch(g.n, args.batch, args.seed)
    t0 = time.time()
    results = client.request(batch)
    dt = time.time() - t0
    assert svc.traces == warm_traces, (
        f"admission re-traced: {svc.traces - warm_traces}")
    lp = svc.last_pump
    if args.batch <= 3 * max(args.buckets):
        assert lp["slices"] == 1, (
            f"expected one executor run, got {lp['slices']}")
    print(f"[serve-graph] {len(results)} mixed queries "
          f"(sssp={lp['lanes_sssp']} ppr={lp['lanes_ppr']} "
          f"ego={sum(r.query.kind == 'ego' for r in results)}) in "
          f"{dt:.2f}s — {lp['slices']} executor run(s), "
          f"bucket={svc.last_batch['bucket']}, "
          f"{lp['n_supersteps']} supersteps, zero re-traces, "
          f"{len(results) / dt:.1f} q/s")

    # -- 3. streamed 1% churn, folded between supersteps ------------------
    delta = _churn_delta(g, args.churn, args.seed)
    svc.mutate(delta)
    probe = [Query("sssp", 17), Query("ppr", 23), Query("ego", 5)]
    t0 = time.time()
    post = client.request(probe + batch)      # fold + serve in one pump
    dt = time.time() - t0
    assert svc.epoch == 1
    assert all(r.epoch == 1 for r in post), "batch straddled the fold"
    assert svc.traces == warm_traces, (
        f"fold re-traced: {svc.traces - warm_traces}")
    print(f"[serve-graph] folded {len(delta.rem_src):,d} removals + "
          f"{len(delta.add_src):,d} adds and re-answered "
          f"{len(post)} queries in {dt:.2f}s (epoch {svc.epoch}, "
          f"zero re-traces)")

    if args.skip_parity:
        print("[serve-graph] OK (parity skipped)")
        return

    # -- 4. post-fold answers vs a fresh full partition() -----------------
    g2 = svc.snapshot_graph()
    t0 = time.time()
    pg2 = partition(g2, args.workers, tau=svc.pg.tau, seed=args.seed,
                    layout="csr", balance="edges")
    t_full = time.time() - t0
    eng = Engine(cfg)
    rr = eng.run("sssp", pg2, source=int(pg2.perm[17]))
    want = np.asarray(rr.state).reshape(-1)[pg2.perm]
    got = post[0].value
    assert np.allclose(got, want, equal_nan=True), "sssp diverged from " \
        "fresh-partition run after the fold"

    deg = np.bincount(g2.src, minlength=g2.n)
    pr = np.zeros(g2.n)
    pr[23] = 1.0
    restart = pr.copy()
    for _ in range(args.ppr_iters):
        contrib = np.where(deg > 0, pr / np.maximum(deg, 1), 0.0)
        inbox = np.zeros(g2.n)
        np.add.at(inbox, g2.dst, contrib[g2.src])
        pr = svc.ppr_alpha * restart + (1 - svc.ppr_alpha) * inbox
    assert np.allclose(post[1].value, pr, atol=1e-5), "ppr diverged"

    res_cc = eng.run("hashmin", pg2)
    roots = canonical_labels(pg2, res_cc.state)
    sizes = np.bincount(roots, minlength=g2.n)
    assert post[2].value == (int(roots[5]), int(sizes[roots[5]])), \
        "ego diverged"
    print(f"[serve-graph] post-fold parity vs fresh partition() OK "
          f"(full re-partition takes {t_full:.2f}s)")
    print("[serve-graph] OK")


if __name__ == "__main__":
    main()

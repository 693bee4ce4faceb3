"""Graph-analytics driver — the paper-kind end-to-end workload.

    PYTHONPATH=src python -m repro.launch.graph_run --algo hashmin \
        --graph powerlaw --n 100000 --workers 32 --tau auto

Runs a full BSP computation with the chosen channel configuration and
reports the paper's metrics: total messages under each channel mode,
per-worker balance, supersteps, wall time.

``--devices D`` runs the sharded executor (core/exec.py): the worker axis
is sharded over a D-device mesh and the channel joins lower to real
collectives.  On CPU the driver forces D host devices via XLA_FLAGS, so
args are parsed *before* jax is imported — keep the repro imports lazy.
"""
from __future__ import annotations

import argparse
import time

GRAPH_NAMES = ("powerlaw", "road", "erdos")
ALGOS = ("hashmin", "pagerank", "sv", "sssp", "msf", "attr_bcast", "gcn")


def make_graph(graph: str, n: int, seed: int):
    import numpy as np
    from repro.graph import generators as gen
    if graph == "powerlaw":
        return gen.powerlaw(n, avg_deg=8, seed=seed)
    if graph == "road":
        return gen.grid_road(int(np.sqrt(n)), seed=seed, weighted=True)
    return gen.erdos(n, avg_deg=16, seed=seed)


def build(graph: str, n: int, seed: int, M: int, tau_arg: str,
          layout: str = "padded", balance: str = "hash",
          split_factor: float = 1.2, hosts: int = 0):
    from repro.core.cost_model import choose_tau
    from repro.graph.structs import partition
    g = make_graph(graph, n, seed)
    g = g.symmetrized()
    deg = g.out_degrees()
    if tau_arg == "auto":
        tau = choose_tau(deg, M)
    elif tau_arg == "off":
        tau = None
    else:
        tau = int(tau_arg)
    pg = partition(g, M, tau=tau, seed=seed, layout=layout,
                   balance=balance, split_factor=split_factor,
                   hosts=hosts if hosts > 1 else None)
    return g, pg, tau


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algo", default="hashmin", choices=list(ALGOS))
    ap.add_argument("--graph", default="powerlaw", choices=list(GRAPH_NAMES))
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--workers", type=int, default=32)
    ap.add_argument("--tau", default="auto")
    ap.add_argument("--no-mirroring", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="dense", choices=["dense", "pallas"],
                    help="combine-channel implementation: dense vmap "
                         "scatters or the plan-driven segment_combine path")
    ap.add_argument("--layout", default="padded", choices=["padded", "csr"],
                    help="edge representation: padded (M, E_loc) rows "
                         "(reference) or flat csr arrays + row offsets "
                         "(O(E + M + n) host memory)")
    ap.add_argument("--balance", default="hash",
                    choices=["hash", "edges", "edges+refine", "split",
                             "vertex-cut"],
                    help="vertex->worker placement: random hash "
                         "(reference), greedy edge-count-balanced, "
                         "edges + greedy crossness-descent locality "
                         "refinement, edge-balanced + hot-worker "
                         "splitting (csr only), or edges + mega-hub "
                         "vertex-cut (state-row splitting via forced "
                         "mirroring)")
    ap.add_argument("--split-factor", type=float, default=1.2,
                    help="split workers whose edge load exceeds this "
                         "multiple of the mean (balance=split)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard the worker axis over this many devices "
                         "(0 = single-device batched simulation); on CPU "
                         "the required host devices are forced via "
                         "XLA_FLAGS")
    ap.add_argument("--hosts", type=int, default=0,
                    help="arrange --devices D as a hierarchical "
                         "(hosts, D/hosts) mesh: the partition becomes "
                         "host-topology-aware, every routed exchange "
                         "combines/dedups per level, and only the "
                         "combined residue crosses the host axis; the "
                         "driver prints intra- vs cross-host "
                         "exchange-volume stats")
    ap.add_argument("--feat-dim", type=int, default=32,
                    help="gcn: embedding feature dimension F — the "
                         "vector-payload width every channel join "
                         "carries as a trailing (lanes, F) block")
    ap.add_argument("--hidden", type=int, default=64,
                    help="gcn: hidden width of the 2-layer GCN")
    ap.add_argument("--classes", type=int, default=8,
                    help="gcn: number of synthetic label classes")
    ap.add_argument("--epochs", type=int, default=10,
                    help="gcn: full-graph AdamW steps")
    ap.add_argument("--pipeline", action="store_true",
                    help="double-buffer the supersteps: chunk every "
                         "routed exchange so chunk k's all_to_all "
                         "overlaps chunk k-1's local combine (results "
                         "keep the parity contract)")
    args = ap.parse_args()

    if args.hosts > 1 and (not args.devices or args.devices % args.hosts):
        ap.error(f"--hosts {args.hosts} needs --devices divisible by it")
    if args.devices > 1:
        from repro.launch.xla_flags import force_host_devices
        force_host_devices(args.devices)

    # jax initializes on first repro import — after the flags above
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import numpy as np
    from repro.api import Engine
    from repro.core.cost_model import straggler_report

    g, pg, tau = build(args.graph, args.n, args.seed, args.workers, args.tau,
                       layout=args.layout, balance=args.balance,
                       split_factor=args.split_factor, hosts=args.hosts)
    if args.hosts > 1 and args.devices:
        dev = (args.hosts, args.devices // args.hosts)
        dev_tag = f"{dev[0]}x{dev[1]}"
    else:
        dev = args.devices if args.devices else None
        dev_tag = str(dev or 1)
    pipe = args.pipeline
    print(f"[graph] {args.graph}: n={g.n} m={g.m} M={args.workers} "
          f"tau={tau} max_deg={int(g.out_degrees().max())} "
          f"backend={args.backend} layout={args.layout} "
          f"balance={args.balance} devices={dev_tag} "
          f"pipeline={'on' if pipe else 'off'}")

    def report_balance(pg_run):
        # printed for the partition the algorithm actually ran (sssp/msf
        # rebuild a weighted partition)
        rep = straggler_report(pg_run.edge_load(phys=True))
        print(f"[balance] {args.balance}: workers {pg_run.M} -> "
              f"{pg_run.M_phys} physical shards; edge-load max/mean="
              f"{rep['max_over_mean']:.2f} cv={rep['cv']:.2f}")
        if dev and args.layout == "csr":
            from repro.core.exec import device_edge_loads
            dl = straggler_report(device_edge_loads(pg_run, dev))
            print(f"[balance] device edge-load max/mean="
                  f"{dl['max_over_mean']:.2f} over {dev_tag} devices")
        from repro.core.exec import crossness_report
        cr = crossness_report(pg_run, dev)
        line = (f"[crossness] cross-worker message fraction="
                f"{cr['cross_worker_frac']:.3f}")
        if "cross_device_frac" in cr:
            line += f" cross-device={cr['cross_device_frac']:.3f}"
        if "cross_host_frac" in cr:
            line += f" cross-host={cr['cross_host_frac']:.3f}"
        print(line)

    mirror = not args.no_mirroring and tau is not None
    be = args.backend
    eng = Engine(backend=be, layout=args.layout, balance=args.balance,
                 split_factor=args.split_factor,
                 hosts=args.hosts if args.hosts > 1 else None,
                 devices=dev, pipeline=pipe, use_mirroring=mirror)

    t0 = time.time()
    if args.algo == "sssp":
        gw = make_graph(args.graph, args.n, args.seed)
        if gw.weight is None:
            gw.weight = np.ones(gw.m, np.float32)
        pg = eng.partition(gw.symmetrized(), args.workers, tau=tau,
                           seed=args.seed)
        res = eng.run("sssp", pg, source=int(pg.perm[0]))
    elif args.algo == "msf":
        gw = make_graph(args.graph, args.n, args.seed)
        if gw.weight is None:
            rng = np.random.RandomState(args.seed)
            gw.weight = rng.rand(gw.m).astype(np.float32) + 0.01
        pg = eng.partition(gw.symmetrized(), args.workers, tau=None,
                           seed=args.seed)
        res = eng.run("msf", pg)
        print(f"[msf] total weight {float(res.state[1]):.2f}, "
              f"{int(res.state[2])} edges")
    elif args.algo == "gcn":
        from repro.core.gspmm import gspmm_sharded
        from repro.train.gcn import normalize_adjacency
        gw = normalize_adjacency(
            make_graph(args.graph, args.n, args.seed).symmetrized())
        pg = eng.partition(gw, args.workers, tau=tau, seed=args.seed)
        res = eng.run("gcn", pg, feat_dim=args.feat_dim,
                      hidden=args.hidden, n_classes=args.classes,
                      epochs=args.epochs, seed=args.seed)
        losses = res.history
        print(f"[gcn] F={args.feat_dim} hidden={args.hidden} "
              f"classes={args.classes}: loss "
              f"{losses[0]:.4f} -> {losses[-1]:.4f} over "
              f"{args.epochs} epochs")
        # message accounting for ONE aggregation join (the training step
        # runs 4 per epoch: 2 forward + 2 backward-cotangent joins)
        _, res.stats = gspmm_sharded(pg, "u_mul_e_sum",
                                     res.state["emb"],
                                     devices=dev or 1, backend=be,
                                     pipeline=pipe, use_mirroring=mirror)
    elif args.algo == "attr_bcast":
        import jax.numpy as jnp
        attr = jnp.arange(pg.n_pad,
                          dtype=jnp.float32).reshape(pg.M, pg.n_loc)
        res = eng.run("attr_bcast", pg, attr=attr)
        res.n_supersteps = 2    # request + respond rounds
    else:
        params = {"n_iters": 30} if args.algo == "pagerank" else {}
        res = eng.run(args.algo, pg, **params)
    stats, n_ss = res.stats, res.n_supersteps
    dt = time.time() - t0

    report_balance(pg)
    print(f"[run] {args.algo}: {int(n_ss)} supersteps in {dt:.2f}s")
    for k in ("msgs_total", "msgs_combined", "msgs_mirror", "msgs_basic",
              "msgs_rr"):
        if k in stats:
            print(f"  {k:16s} {int(stats[k]):>14,d}")
    for k in ("per_worker_total", "per_worker_rr", "per_worker_basic"):
        if k in stats:
            rep = straggler_report(np.asarray(stats[k]))
            print(f"  balance[{k}]: max/mean={rep['max_over_mean']:.2f} "
                  f"cv={rep['cv']:.2f} gini={rep['gini']:.3f}")

    if dev:
        # static wire-lane accounting of the per-superstep exchanges;
        # on a hierarchical mesh cross_host counts only the post-combine
        # residue that actually crosses the host axis
        from repro.core.exec import broadcast_plan_kinds
        from repro.core.exec import exchange_volume_report
        vol = exchange_volume_report(
            pg, dev, plan_kinds=broadcast_plan_kinds(be, mirror))
        print(f"[exchange] devices={dev_tag}: wire lanes/superstep "
              f"total={vol['total']:,d} intra_host={vol['intra_host']:,d} "
              f"cross_host={vol['cross_host']:,d}")
        for name, e in sorted(vol["per_exchange"].items()):
            print(f"  {name:16s} intra={e['intra_host']:>12,d} "
                  f"cross={e['cross_host']:>12,d}")


if __name__ == "__main__":
    main()

# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# ``--smoke`` runs a fast invariant-checking mode for CI: it asserts the
# paper's message-count theorems, dense/pallas backend parity, and sharded
# executor parity on small graphs and writes the numbers to a JSON
# artifact.  ``--graph-bench`` records the perf trajectory (wall time +
# message counts for every backend x layout x device-count cell) to
# BENCH_graph.json.
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# jax-free: safe to import before the flags are set
from repro.launch.xla_flags import force_host_devices  # noqa: E402


def smoke(out_path: str, scale: int = 4000, M: int = 8) -> None:
    import numpy as np
    import jax.numpy as jnp
    from repro.algorithms.hashmin import hashmin
    from repro.algorithms.sv import sv
    from repro.core.cost_model import choose_tau, thm1_bound
    from repro.graph import generators as gen
    from repro.graph.structs import partition

    report = {"scale": scale, "workers": M, "checks": {}}

    def check(name, ok, **numbers):
        report["checks"][name] = {"ok": bool(ok),
                                  **{k: int(v) for k, v in numbers.items()}}
        status = "ok" if ok else "FAIL"
        print(f"[smoke] {name}: {status} "
              + " ".join(f"{k}={int(v):,d}" for k, v in numbers.items()))
        assert ok, name

    g = gen.powerlaw(scale, avg_deg=8, seed=5, alpha=1.8).symmetrized()
    tau = choose_tau(g.out_degrees(), M)
    pg = partition(g, M, tau=tau, seed=0)
    deg = np.asarray(pg.deg)

    stats = {}
    n_ss = 0
    for backend in ("dense", "pallas"):
        _, stats[backend], n_ss = hashmin(pg, backend=backend)

    s = stats["dense"]
    # combining only ever removes messages
    check("combined_le_basic",
          int(s["msgs_combined"]) <= int(s["msgs_basic"]),
          combined=s["msgs_combined"], basic=s["msgs_basic"])
    # Theorem 1: each mirrored broadcast costs <= min(M, d(v)) messages;
    # summed over active mirrored vertices and supersteps it is bounded by
    # supersteps * sum over mirrored v of min(M, d(v))
    nmir = int((np.asarray(pg.mir_ids) < pg.n_pad).sum())
    per_v_bound = sum(thm1_bound(M, int(d))
                      for d in deg.reshape(-1)[np.asarray(pg.mir_ids)[:nmir]])
    check("thm1_mirror_bound",
          int(s["msgs_mirror"]) <= int(n_ss) * per_v_bound,
          mirror=s["msgs_mirror"], bound=int(n_ss) * per_v_bound)
    # mirroring beats pure combining on the skewed graph (Fig. 12 effect)
    _, s_nom, _ = hashmin(pg, use_mirroring=False)
    check("mirroring_reduces_total",
          int(s["msgs_total"]) <= int(s_nom["msgs_combined"]),
          mirrored=s["msgs_total"], no_mirroring=s_nom["msgs_combined"])
    # backend parity: the pallas plan path must not change a single count
    parity = all(
        np.array_equal(np.asarray(stats["dense"][k]),
                       np.asarray(stats["pallas"][k]))
        for k in stats["dense"])
    check("backend_parity", parity,
          dense_total=stats["dense"]["msgs_total"],
          pallas_total=stats["pallas"]["msgs_total"])
    # layout parity: the flat csr representation must not change a count
    pg_csr = partition(g, M, tau=tau, seed=0, layout="csr")
    _, s_csr, _ = hashmin(pg_csr, backend="pallas")
    layout_parity = all(
        np.array_equal(np.asarray(stats["dense"][k]), np.asarray(s_csr[k]))
        for k in stats["dense"])
    check("layout_parity", layout_parity,
          padded_total=stats["dense"]["msgs_total"],
          csr_total=s_csr["msgs_total"])
    # Theorem 3: request-respond never exceeds basic in S-V
    pg_sv = partition(g, M, tau=None, seed=0)
    _, s_sv, _ = sv(pg_sv, backend="pallas")
    check("thm3_rr_le_basic", int(s_sv["msgs_rr"]) <= int(s_sv["msgs_basic"]),
          rr=s_sv["msgs_rr"], basic=s_sv["msgs_basic"])

    # sharded executor parity: the worker mesh must not change a label or
    # a single message count (dense all_to_all join, 8 forced host devices)
    labels_1, _, _ = hashmin(pg_csr, backend="dense")
    labels_8, s_sh, _ = hashmin(pg_csr, backend="dense", devices=8)
    sharded_parity = (np.array_equal(np.asarray(labels_1),
                                     np.asarray(labels_8))
                      and all(np.array_equal(np.asarray(stats["dense"][k]),
                                             np.asarray(s_sh[k]))
                              for k in stats["dense"]))
    check("sharded_parity", sharded_parity,
          devices1_total=stats["dense"]["msgs_total"],
          devices8_total=s_sh["msgs_total"])

    Path(out_path).write_text(json.dumps(report, indent=2))
    print(f"[smoke] all invariants hold; report -> {out_path}")


def graph_bench(out_path: str, n: int = 200_000, M: int = 8,
                device_counts=(1, 8, (2, 4))) -> None:
    """Perf-trajectory artifact: wall time + message counts for every
    algo x backend x layout x device-count cell — D=8 both as the flat
    1-D mesh and as the hierarchical 2x4 (host, device) mesh — plus the
    per-device compiled-buffer stats of every sharded channel family at
    D=8, and two HARD gates: (a) no sharded channel may
    all-reduce/all-gather an operand of >= n_pad elements (a replicated
    global buffer would void the paper's per-worker communication
    bounds); (b) the cross-host wire volume of the hierarchical static
    exchanges must stay strictly below the flat 1-D all-pairs volume —
    the per-level combine must actually remove traffic from the
    expensive axis.  Wall times include the per-call jit compile (each
    cell builds a fresh step closure) — they are trend numbers, not
    steady-state throughput."""
    from repro.algorithms.hashmin import hashmin
    from repro.algorithms.pagerank import pagerank
    from repro.core.cost_model import choose_tau
    from repro.core.exec import broadcast_plan_kinds
    from repro.core.exec import exchange_volume_report
    from repro.graph import generators as gen
    from repro.graph.structs import partition
    from repro.launch.shard_check import routed_memory_report

    g = gen.powerlaw(n, avg_deg=8, seed=5, alpha=1.8).symmetrized()
    tau = choose_tau(g.out_degrees(), M)
    report = {"n": g.n, "m": g.m, "workers": M, "tau": int(tau),
              "cells": [], "memory": {}, "exchange_volume": {}}
    for layout in ("padded", "csr"):
        pg = partition(g, M, tau=tau, seed=0, layout=layout)
        # per-device peak live-buffer bytes + collective operand sizes of
        # the compiled sharded channels (the routed-exchange artifact)
        flat_counts = [d for d in device_counts if not isinstance(d, tuple)]
        mem = routed_memory_report(pg, devices=max(flat_counts))
        report["memory"][layout] = mem
        n_pad = pg.n_pad
        for prog, entry in mem["programs"].items():
            worst = entry["collective_max_elems"]
            bad = max(worst["all-reduce"], worst["all-gather"])
            print(f"[graph-bench] memory {layout}/{prog}: "
                  f"worst replicated collective operand {bad:,d} elems, "
                  f"temp {entry.get('temp_bytes', -1):,d} B")
            assert bad < n_pad, (
                f"{layout}/{prog}: replicated collective operand of "
                f"{bad} elems >= n_pad {n_pad} — a sharded channel is "
                f"replicating global state again")
        if layout == "csr":
            # static wire-lane accounting of the per-superstep exchanges
            # (plan legs + fetch plans, pallas kinds): the flat D=8 mesh
            # treats every device pair alike; on the 2-D meshes only the
            # post-combine residue crosses the host axis
            kinds = broadcast_plan_kinds("pallas")
            vols = {"8": exchange_volume_report(pg, 8, kinds),
                    "2x4": exchange_volume_report(pg, (2, 4), kinds),
                    "4x2": exchange_volume_report(pg, (4, 2), kinds)}
            report["exchange_volume"] = vols
            flat_total = vols["8"]["total"]
            for tag in ("2x4", "4x2"):
                cross = vols[tag]["cross_host"]
                print(f"[graph-bench] exchange-volume {tag}: "
                      f"cross_host={cross:,d} intra_host="
                      f"{vols[tag]['intra_host']:,d} vs flat all-pairs "
                      f"{flat_total:,d} lanes")
                assert cross < flat_total, (
                    f"{tag}: cross-host volume {cross} >= flat all-pairs "
                    f"volume {flat_total} — the per-level combine is not "
                    f"removing traffic from the host axis")
        for backend in ("dense", "pallas"):
            for algo, fn in (("hashmin", hashmin),
                             ("pagerank", lambda p, **kw: pagerank(
                                 p, n_iters=10, tol=0.0, **kw))):
                for D in device_counts:
                    dev = None if D == 1 else D
                    tag = ("x".join(str(d) for d in D)
                           if isinstance(D, tuple) else D)
                    t0 = time.perf_counter()
                    _, stats, n_ss = fn(pg, backend=backend, devices=dev)
                    wall = time.perf_counter() - t0
                    cell = {"algo": algo, "backend": backend,
                            "layout": layout, "devices": tag,
                            "wall_s": round(wall, 3),
                            "supersteps": int(n_ss),
                            "msgs_total": int(stats["msgs_total"]),
                            "msgs_basic": int(stats["msgs_basic"])}
                    report["cells"].append(cell)
                    print(f"[graph-bench] {algo}/{layout}/{backend}/"
                          f"devices={tag}: {wall:.2f}s "
                          f"msgs={cell['msgs_total']:,d}")
    # the mesh is a representation choice: message counts must agree
    # across every cell of one algo
    for algo in ("hashmin", "pagerank"):
        totals = {c["msgs_total"] for c in report["cells"]
                  if c["algo"] == algo}
        assert len(totals) == 1, f"{algo}: msgs_total diverged {totals}"
    Path(out_path).write_text(json.dumps(report, indent=2))
    print(f"[graph-bench] report -> {out_path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: assert the paper's message-count "
                         "invariants + backend/layout/sharded parity, "
                         "emit JSON")
    ap.add_argument("--graph-bench", action="store_true",
                    help="record wall time + message counts for every "
                         "backend x layout x device-count cell")
    ap.add_argument("--n", type=int, default=200_000,
                    help="graph size (graph-bench mode)")
    ap.add_argument("--out", default="bench-smoke.json",
                    help="JSON report path (smoke / graph-bench mode)")
    args = ap.parse_args()
    if args.smoke or args.graph_bench:
        force_host_devices(8)      # before the first jax import
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        smoke(args.out)
        return
    if args.graph_bench:
        graph_bench(args.out, n=args.n)
        return

    from benchmarks import (bench_balance, bench_kernels, bench_mirroring,
                            bench_reqresp, bench_roofline)
    suites = [
        ("fig12_mirroring", bench_mirroring.run),
        ("fig13_reqresp", bench_reqresp.run),
        ("fig1_2_balance", bench_balance.run),
        ("kernels", bench_kernels.run),
        ("roofline", bench_roofline.run),
    ]
    failures = []
    for name, fn in suites:
        print(f"\n### {name}")
        try:
            fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED suites: {failures}")
        raise SystemExit(1)
    print("\nALL BENCHMARK SUITES PASSED")


if __name__ == '__main__':
    main()

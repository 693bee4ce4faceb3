"""Load-balance benchmarks.

Two modes:

* ``run()`` (default CLI) — paper Figs. 1-2: per-worker sent-message
  histograms (Hash-Min with/without mirroring, S-V request-respond vs
  basic), printed as CSV for plotting.
* ``balance_gate()`` (``--gate``) — the partitioner trajectory the CI
  ``bench-balance`` job pins: on the n=200k power-law graph at M=64 it
  partitions with ``balance`` in {hash, edges, edges+refine, split},
  records per-worker / per-physical-shard / per-device edge loads,
  cross-worker / cross-device message fractions
  (``exec.crossness_report``), wall times, and message totals to
  ``BENCH_balance.json``, and **asserts** (hard gates, not advisory):

  - ``balance="split"`` per-worker edge-load max_over_mean <= 1.25
    (the hash baseline on this graph is degree-skew-proportional, ~7x);
  - the locality refinement pass strictly reduces the cross-device
    message fraction vs plain ``edges`` at equal-or-better per-worker
    edge-load max_over_mean (locality must never be bought with
    imbalance — the refiner's load cap, asserted here);
  - algorithm outputs are identical across all modes (canonicalized
    to original-vertex space — the modes only move vertices);
  - ``edges`` and ``split`` agree on every raw message count: splitting
    re-shards combining, it never invents or loses a basic message.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from benchmarks.common import paper_graphs, row, timed  # noqa: E402
from repro.api import Engine, config_of  # noqa: E402
from repro.algorithms.hashmin import hashmin  # noqa: E402
from repro.algorithms.sv import sv  # noqa: E402
from repro.core.cost_model import (choose_tau, predicted_balance,  # noqa: E402
                                   straggler_report, vertex_cost)
from repro.graph.structs import canonical_labels, partition  # noqa: E402

M = 16

GATE_MAX_OVER_MEAN = 1.25


def run(scale=20_000):
    print("# Fig1/2: name,us_per_call,maxmean|cv|hist")
    graphs = paper_graphs(scale)

    g = graphs["btc_like"].symmetrized()
    tau = choose_tau(g.out_degrees(), M)
    per_backend = {}
    for label, tau_i, mirror in [("noM", None, False), ("mirrored", tau, True)]:
        pg = partition(g, M, tau=tau_i, seed=0)
        for backend in ("dense", "pallas"):
            (res, stats, n), secs = timed(hashmin, pg, use_mirroring=mirror,
                                          backend=backend)
            per = np.asarray(stats["per_worker_total"] if mirror
                             else stats["per_worker_combined"])
            per_backend[(label, backend)] = per
            rep = straggler_report(per)
            hist = "|".join(str(int(x)) for x in per)
            row(f"fig1.hashmin.btc_like.{label}.{backend}", secs,
                f"maxmean={rep['max_over_mean']:.2f};"
                f"cv={rep['cv']:.2f};{hist}")
        # the plan backend must not change the balance picture at all
        assert np.array_equal(per_backend[(label, "dense")],
                              per_backend[(label, "pallas")]), label
    # the edge-balanced partitioner must beat the hash baseline on the
    # skewed graph without changing the component labels
    pg_h = partition(g, M, tau=None, seed=0, layout="csr")
    pg_s = partition(g, M, tau=None, seed=0, layout="csr", balance="split",
                     split_factor=1.1)
    bal_h = straggler_report(pg_h.edge_load())
    bal_s = straggler_report(pg_s.edge_load(phys=True))
    row("fig1.partition.btc_like.hash", 0.0,
        f"maxmean={bal_h['max_over_mean']:.2f}")
    row("fig1.partition.btc_like.split", 0.0,
        f"maxmean={bal_s['max_over_mean']:.2f}")
    assert bal_s["max_over_mean"] <= bal_h["max_over_mean"] + 1e-9

    g = graphs["usa_like"].symmetrized()
    pg = partition(g, M, tau=None, seed=0)
    (labels, stats, n), secs = timed(sv, pg)
    for label, key in [("basic", "per_worker_basic"), ("reqresp",
                                                       "per_worker_rr")]:
        per = np.asarray(stats[key])
        rep = straggler_report(per)
        hist = "|".join(str(int(x)) for x in per)
        row(f"fig2.sv.usa_like.{label}", secs,
            f"maxmean={rep['max_over_mean']:.2f};cv={rep['cv']:.2f};{hist}")
    return True


def balance_gate(n: int = 200_000, workers: int = 64, devices: int = 8,
                 out: str = "BENCH_balance.json",
                 split_factor: float = 1.1) -> dict:
    """The CI load-balance trajectory (hard gate)."""
    from repro.core.exec import crossness_report, device_edge_loads
    from repro.graph import generators as gen

    t0 = time.perf_counter()
    g = gen.powerlaw(n, avg_deg=8, seed=5, alpha=1.8).symmetrized()
    gen_s = time.perf_counter() - t0
    report = {"n": g.n, "m": g.m, "workers": workers, "devices": devices,
              "split_factor": split_factor, "gen_s": round(gen_s, 2),
              "gate_max_over_mean": GATE_MAX_OVER_MEAN, "modes": {}}

    results = {}
    for mode in ("hash", "edges", "edges+refine", "split"):
        t0 = time.perf_counter()
        # tau=None isolates the partitioner: with mirroring on, Ch_mir
        # already spreads the hubs' fan-out (Figs. 1-2); without it the
        # assignment and the split boundaries must carry the skew alone.
        pg = partition(g, workers, tau=None, seed=0, layout="csr",
                       balance=mode, split_factor=split_factor)
        part_s = time.perf_counter() - t0
        loads = pg.edge_load()
        ploads = pg.edge_load(phys=True)
        t0 = time.perf_counter()
        res = Engine(config_of(pg, use_mirroring=False,
                               backend="pallas")).run("hashmin", pg)
        labels, stats, n_ss = res.state, res.stats, res.n_supersteps
        run_s = time.perf_counter() - t0
        cell = {
            "partition_s": round(part_s, 2),
            "hashmin_s": round(run_s, 2),
            "supersteps": int(n_ss),
            "M_phys": int(pg.M_phys),
            "worker_load": straggler_report(loads),
            "phys_load": straggler_report(ploads),
            "device_load": straggler_report(
                device_edge_loads(pg, devices)),
            "msgs_basic": int(stats["msgs_basic"]),
            "msgs_combined": int(stats["msgs_combined"]),
            "msgs_total": int(stats["msgs_total"]),
            "crossness": crossness_report(pg, devices),
        }
        # the cost model's a-priori prediction for this assignment, next
        # to the realized loads it is supposed to anticipate
        assign = np.asarray(pg.perm) // pg.n_loc
        cell["predicted"] = predicted_balance(
            vertex_cost(g.out_degrees(), workers, None), assign, workers)
        report["modes"][mode] = cell
        results[mode] = (pg, np.asarray(labels), stats)
        print(f"[balance] {mode}: partition {part_s:.1f}s, hashmin "
              f"{run_s:.1f}s/{int(n_ss)} ss, M_phys={pg.M_phys}, "
              f"edge-load max/mean={cell['phys_load']['max_over_mean']:.3f}"
              f" (workers {cell['worker_load']['max_over_mean']:.3f}), "
              f"device max/mean="
              f"{cell['device_load']['max_over_mean']:.3f}, "
              f"cross-device frac="
              f"{cell['crossness']['cross_device_frac']:.4f}, "
              f"msgs={cell['msgs_total']:,d}")

    # --- correctness invariants (identical outputs, honest accounting) --
    canon = {m: canonical_labels(pg, lab) for m, (pg, lab, _) in
             results.items()}
    for mode in ("edges", "edges+refine", "split"):
        assert np.array_equal(canon["hash"], canon[mode]), \
            f"{mode} balance changed the components"
    # same assignment => bitwise-identical labels and identical raw counts
    assert np.array_equal(results["edges"][1], results["split"][1]), \
        "splitting changed a label bit"
    assert (report["modes"]["edges"]["msgs_basic"]
            == report["modes"]["split"]["msgs_basic"]), \
        "splitting changed the basic message count"

    # --- the hard gates --------------------------------------------------
    baseline = report["modes"]["hash"]["phys_load"]["max_over_mean"]
    split_mm = report["modes"]["split"]["phys_load"]["max_over_mean"]
    edges_cd = report["modes"]["edges"]["crossness"]["cross_device_frac"]
    ref_cd = report["modes"]["edges+refine"]["crossness"][
        "cross_device_frac"]
    edges_mm = report["modes"]["edges"]["worker_load"]["max_over_mean"]
    ref_mm = report["modes"]["edges+refine"]["worker_load"][
        "max_over_mean"]
    report["gate_ok"] = bool(split_mm <= GATE_MAX_OVER_MEAN)
    report["gate_refine_crossness_ok"] = bool(ref_cd < edges_cd)
    # refinement never buys locality with imbalance: equal-or-better
    # load balance than the assignment it refines (its load cap)
    report["gate_refine_balance_ok"] = bool(ref_mm <= edges_mm + 1e-9)
    print(f"[balance] GATE: hash baseline max/mean={baseline:.3f} -> "
          f"split {split_mm:.3f} (gate <= {GATE_MAX_OVER_MEAN})")
    print(f"[balance] GATE: cross-device fraction edges {edges_cd:.4f} "
          f"-> refined {ref_cd:.4f} (strictly less) at worker max/mean "
          f"{ref_mm:.3f} vs edges {edges_mm:.3f} (equal or better)")
    # report lands on disk BEFORE any gate can abort the job
    Path(out).write_text(json.dumps(report, indent=2))
    print(f"[balance] report -> {out}")
    assert report["gate_ok"], (
        f"balance gate FAILED: split per-worker edge-load max_over_mean "
        f"{split_mm:.3f} > {GATE_MAX_OVER_MEAN}")
    assert report["gate_refine_crossness_ok"], (
        f"refine gate FAILED: refined cross-device fraction {ref_cd:.4f} "
        f"not < unrefined {edges_cd:.4f}")
    assert report["gate_refine_balance_ok"], (
        f"refine gate FAILED: refined worker edge-load max_over_mean "
        f"{ref_mm:.3f} > edges {edges_mm:.3f} (locality bought with "
        f"imbalance)")
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="run the CI load-balance gate instead of the "
                         "Fig. 1/2 histograms")
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--workers", type=int, default=64)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--split-factor", type=float, default=1.1)
    ap.add_argument("--out", default="BENCH_balance.json")
    args = ap.parse_args()
    if args.gate:
        balance_gate(n=args.n, workers=args.workers, devices=args.devices,
                     out=args.out, split_factor=args.split_factor)
    else:
        run()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

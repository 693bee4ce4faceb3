"""gSpMM channel join vs the dense segment-sum baseline at GNN scale,
plus the end-to-end GCN training check (PR 8).

Measures one ``u_mul_e_sum`` aggregation — feats ``(n, F=32)`` on an
n=200k power-law graph — two ways:

* ``dense_segment_sum``: the straight-line XLA formulation,
  ``zeros.at[dst].add(x[src] * w)`` over the flat edge list (what a
  GNN library does on one device);
* ``channel_join``: the same aggregation as a sharded message-channel
  join (sender-side combining + mirror fan-out) over a D=8 device mesh
  via ``exec.build_apply``.

Numeric parity between the two is **hard-asserted on every run** (report
mode included) — the join is an execution strategy, never a different
operator.  ``--gate`` additionally asserts the GCN trains: 5 full-graph
epochs at n=200k / F=32 / devices=8 must strictly decrease the loss.

Methodology (single-CPU runners): both programs are compiled ONCE and
timed samples are INTERLEAVED — a co-tenant degrades both contenders
instead of poisoning one; best sample per program is kept.  Wall-clock
on a forced 8-device CPU host measures collective scheduling overhead,
not network overlap — the paper-relevant numbers are the message/lane
accounting also recorded here.

    python benchmarks/bench_gspmm.py                 # report mode
    python benchmarks/bench_gspmm.py --gate          # CI hard gate
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# jax-free: safe to import before the device flags are set
from repro.launch.xla_flags import force_host_devices  # noqa: E402


def gspmm_bench(n: int = 200_000, feat_dim: int = 32, workers: int = 32,
                devices: int = 8, epochs: int = 5, repeat: int = 3,
                out: str = "BENCH_gspmm.json", gate: bool = False) -> dict:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from repro.core import exec as exec_mod
    from repro.core import gspmm
    from repro.core.cost_model import choose_tau
    from repro.graph import generators as gen
    from repro.graph.structs import partition
    from repro.train.gcn import normalize_adjacency, train_gcn

    g = gen.powerlaw(n, avg_deg=8, seed=5, alpha=1.8).symmetrized()
    g = normalize_adjacency(g)
    tau = choose_tau(g.out_degrees(), workers)
    pg = partition(g, workers, tau=tau, seed=0, layout="csr")
    rng = np.random.RandomState(0)
    feats = jnp.asarray(
        rng.randn(pg.M, pg.n_loc, feat_dim).astype(np.float32))
    src = jnp.asarray(pg.perm[g.src])
    dst = jnp.asarray(pg.perm[g.dst])
    w = jnp.asarray(g.weight.astype(np.float32))

    report = {"n": g.n, "m": g.m, "F": feat_dim, "workers": workers,
              "devices": devices, "tau": int(tau), "layout": "csr",
              "kind": "u_mul_e_sum", "programs": {}}

    # -- dense baseline: flat scatter-add, one device ---------------------
    def dense(x):
        xf = x.reshape(pg.n_pad, feat_dim)
        outf = jnp.zeros_like(xf).at[dst].add(xf[src] * w[:, None])
        return outf.reshape(x.shape)

    f_dense = jax.jit(dense)

    # -- channel join: sharded mesh, compiled once ------------------------
    def mk(gctx):
        def fn(x):
            return gspmm.gspmm_stats(gctx, "u_mul_e_sum", x)
        return fn

    t0 = time.perf_counter()
    f_join, arrays = exec_mod.build_apply(pg, mk, (feats,),
                                          devices=devices)
    join_out, stats = jax.block_until_ready(f_join(arrays, (feats,)))
    compile_join = time.perf_counter() - t0
    t0 = time.perf_counter()
    dense_out = jax.block_until_ready(f_dense(feats))
    compile_dense = time.perf_counter() - t0

    # -- parity: HARD assert, report mode included ------------------------
    err = float(jnp.max(jnp.abs(join_out - dense_out)))
    scale = float(jnp.max(jnp.abs(dense_out))) or 1.0
    report["parity_max_abs_err"] = err
    report["parity_rel_err"] = err / scale
    assert err <= 1e-4 * scale + 1e-5, (
        f"channel join diverged from dense segment-sum: max |delta| "
        f"{err:.3e} vs scale {scale:.3e}")

    # -- interleaved best-of timing ---------------------------------------
    best = {"dense_segment_sum": float("inf"), "channel_join": float("inf")}
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(f_dense(feats))
        best["dense_segment_sum"] = min(best["dense_segment_sum"],
                                        time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(f_join(arrays, (feats,)))
        best["channel_join"] = min(best["channel_join"],
                                   time.perf_counter() - t0)
    report["programs"]["dense_segment_sum"] = {
        "best_s": round(best["dense_segment_sum"], 4),
        "compile_and_first_run_s": round(compile_dense, 3)}
    report["programs"]["channel_join"] = {
        "best_s": round(best["channel_join"], 4),
        "compile_and_first_run_s": round(compile_join, 3),
        "msgs_total": int(stats["msgs_total"]),
        "msgs_combined": int(stats["msgs_combined"]),
        "msgs_mirror": int(stats["msgs_mirror"]),
        "msgs_basic": int(stats["msgs_basic"])}
    print(f"[gspmm-bench] n={g.n} F={feat_dim} D={devices}: dense "
          f"{best['dense_segment_sum']:.3f}s, channel join "
          f"{best['channel_join']:.3f}s, parity |delta| {err:.2e}, "
          f"msgs {int(stats['msgs_total']):,d} vs basic "
          f"{int(stats['msgs_basic']):,d}", flush=True)

    # -- GCN end-to-end: loss must decrease over >= 5 epochs --------------
    t0 = time.perf_counter()
    _, losses = train_gcn(pg, feat_dim=feat_dim, hidden=64, n_classes=8,
                          epochs=epochs, lr=1e-2, seed=0, devices=devices)
    gcn_s = time.perf_counter() - t0
    report["gcn"] = {"epochs": epochs, "hidden": 64, "classes": 8,
                     "loss_history": [round(x, 5) for x in losses],
                     "wall_s": round(gcn_s, 2)}
    print(f"[gspmm-bench] gcn: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {epochs} epochs ({gcn_s:.1f}s incl. compile)", flush=True)

    # write BEFORE the gate asserts: the JSON is the failure diagnostic
    Path(out).write_text(json.dumps(report, indent=2))
    print(f"[gspmm-bench] report -> {out}")
    if gate:
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], (
            f"GCN loss did not decrease: {losses[0]:.5f} -> "
            f"{losses[-1]:.5f}")
        print("[gspmm-bench] GATE OK: parity exact within tolerance and "
              "GCN loss decreased")
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="hard-fail unless the GCN loss decreases over "
                         "the epoch budget (join/dense parity is "
                         "asserted on every run)")
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--feat-dim", type=int, default=32)
    ap.add_argument("--workers", type=int, default=32)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--out", default="BENCH_gspmm.json")
    args = ap.parse_args()
    force_host_devices(args.devices)    # before the first jax import
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    gspmm_bench(n=args.n, feat_dim=args.feat_dim, workers=args.workers,
                devices=args.devices, epochs=args.epochs,
                repeat=args.repeat, out=args.out, gate=args.gate)


if __name__ == "__main__":
    main()

"""Supersteps/sec for the double-buffered exchange pipeline (PR 6).

Measures the steady-state superstep rate of a fixed-iteration PageRank
(the paper's broadcast/sum workload) on the csr/pallas **sharded**
executor, for devices {1, 8} x pipeline {off, on}, and writes the
figures to ``BENCH_pipeline.json``.  ``--gate`` additionally **asserts**
(hard gate, not a report) that the pipelined path sustains at least
``GATE_MIN_RATIO - GATE_NOISE`` x the sequential supersteps/sec at
every device count: the pipeline must never cost real throughput, and
the threshold is ratcheted as overlap wins land.

Methodology: each (devices, pipeline) cell builds its jitted program
ONCE via ``exec.build_sharded`` and re-invokes the already-compiled
function for every timed sample — per-call re-tracing is what makes
naive wall-clock deltas jitter by 2-3x (the jit compile at n=1M runs
minutes and varies tens of seconds run to run, drowning a 12-superstep
signal).  Timed samples for the sequential and pipelined programs of
one device count are interleaved, so a co-tenant landing on the runner
mid-measurement degrades both paths instead of poisoning one; the best
sample per program is kept.  The step never halts early (``tol`` is
effectively 0), so every sample runs exactly ``--iters`` supersteps.

    python benchmarks/bench_pipeline.py                  # report mode
    python benchmarks/bench_pipeline.py --gate           # CI hard gate
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

# Pipelined supersteps/sec must be >= (GATE_MIN_RATIO - GATE_NOISE) x
# sequential.  On a single CPU host XLA runs the collectives
# synchronously, so the honest expectation is parity minus the copy
# cost of carrying one in-flight exchange through the round loop; the
# ratio gets ratcheted above 1.0 once an async-collective backend
# records a real overlap win.
GATE_MIN_RATIO = 1.0
GATE_NOISE = 0.15


def _build(pg, devices: int, pipeline: bool, n_iters: int,
           damping: float = 0.85):
    """The paper's PageRank broadcast step (cf. algorithms/pagerank),
    fixed iteration count (never halts early), compiled once through
    exec.build_sharded so timed samples rerun the same executable."""
    import jax.numpy as jnp
    from repro.core import exec as exec_mod
    from repro.core.channels import broadcast

    n = pg.n

    def make_step(g):
        deg = jnp.maximum(g.deg, 1)

        def step(state, i):
            pr = state
            contrib = jnp.where(g.vmask, pr / deg, 0.0)
            active = g.vmask & (g.deg > 0)
            inbox, stats = broadcast(g, contrib, active, op="sum",
                                     use_mirroring=True, backend="pallas")
            new_pr = jnp.where(g.vmask,
                               (1 - damping) / n + damping * inbox, 0.0)
            return new_pr, jnp.zeros((), bool), stats
        return step

    pr0 = jnp.where(pg.vmask, 1.0 / n, 0.0)
    fn, args, _ = exec_mod.build_sharded(
        pg, make_step, pr0, n_iters, devices=devices,
        plan_kinds=exec_mod.broadcast_plan_kinds("pallas", True),
        pipeline=pipeline)
    return fn, args


def _measure_device(pg, devices: int, n_iters: int, repeat: int):
    """One devices= cell: compile both programs, then interleave timed
    invocations of the compiled executables."""
    import jax

    progs, compile_s = {}, {}
    for pipe in (False, True):
        fn, args = _build(pg, devices, pipe, n_iters)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        compile_s[pipe] = time.perf_counter() - t0
        progs[pipe] = (fn, args, int(out[2]))

    best = {False: float("inf"), True: float("inf")}
    for _ in range(repeat):
        for pipe in (False, True):
            fn, args, _ = progs[pipe]
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best[pipe] = min(best[pipe], time.perf_counter() - t0)

    cells = []
    for pipe in (False, True):
        n_ss = progs[pipe][2]
        assert n_ss == n_iters, (n_ss, n_iters)
        per_ss = best[pipe] / n_ss
        cells.append({"devices": devices, "pipeline": pipe,
                      "supersteps_per_sec": round(1.0 / per_ss, 3),
                      "sec_per_superstep": round(per_ss, 4),
                      "wall_s": round(best[pipe], 3),
                      "compile_and_first_run_s": round(compile_s[pipe], 3),
                      "supersteps": n_ss})
    return cells


def pipeline_bench(n: int = 1_000_000, workers: int = 32,
                   device_counts=(1, 8), n_iters: int = 12,
                   repeat: int = 2, out: str = "BENCH_pipeline.json",
                   gate: bool = False) -> dict:
    from repro.core.cost_model import choose_tau
    from repro.graph import generators as gen
    from repro.graph.structs import partition

    g = gen.powerlaw(n, avg_deg=8, seed=5, alpha=1.8).symmetrized()
    tau = choose_tau(g.out_degrees(), workers)
    pg = partition(g, workers, tau=tau, seed=0, layout="csr")
    report = {"n": g.n, "m": g.m, "workers": workers, "tau": int(tau),
              "layout": "csr", "backend": "pallas", "algo": "pagerank",
              "n_iters": n_iters, "gate_min_ratio": GATE_MIN_RATIO,
              "gate_noise": GATE_NOISE, "cells": [], "ratios": {}}

    for D in device_counts:
        seq, pipe = _measure_device(pg, D, n_iters, repeat)
        report["cells"] += [seq, pipe]
        ratio = pipe["supersteps_per_sec"] / seq["supersteps_per_sec"]
        report["ratios"][str(D)] = round(ratio, 3)
        print(f"[pipeline-bench] devices={D}: sequential "
              f"{seq['supersteps_per_sec']:.2f} ss/s, pipelined "
              f"{pipe['supersteps_per_sec']:.2f} ss/s "
              f"(ratio {ratio:.3f})", flush=True)

    # write BEFORE asserting: the JSON is the diagnostic when the gate
    # fails
    Path(out).write_text(json.dumps(report, indent=2))
    print(f"[pipeline-bench] report -> {out}")
    if gate:
        floor = GATE_MIN_RATIO - GATE_NOISE
        for D, ratio in report["ratios"].items():
            assert ratio >= floor, (
                f"devices={D}: pipelined supersteps/sec fell to "
                f"{ratio:.3f}x sequential (< {floor:.2f}) — the double "
                f"buffer is costing throughput")
        print(f"[pipeline-bench] GATE OK: pipelined >= {floor:.2f}x "
              f"sequential at every device count")
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", action="store_true",
                    help="hard-fail if pipelined supersteps/sec drops "
                         "below the gate ratio at any device count")
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--workers", type=int, default=32)
    ap.add_argument("--iters", type=int, default=12)
    ap.add_argument("--repeat", type=int, default=2)
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 8])
    ap.add_argument("--out", default="BENCH_pipeline.json")
    args = ap.parse_args()
    force_host_devices(max(args.devices))   # before the first jax import
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    pipeline_bench(n=args.n, workers=args.workers,
                   device_counts=tuple(args.devices), n_iters=args.iters,
                   repeat=args.repeat, out=args.out, gate=args.gate)


if __name__ == "__main__":
    main()

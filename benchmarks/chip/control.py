#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3

For each seed, in one process: the cell's graph and partition as a run
makes them, one job of the program through ``Engine.run``, and its
numbers against the reference (the lower reading); then the control's
numbers against the same reference (the upper reading).  The control is
named by the cell's reference module:

* PageRank: the reference computed in bfloat16, one precision step
  below the configuration's float32 (``refs/pagerank.control``);
* S-V: the program's own job stopped before its last superstep that
  moves a label (``max_supersteps`` two below the converged count; the
  last superstep only confirms the halt), a stale answer where the
  configuration states exact components.

The benchmark's own runs never run this.  Prints one line per seed and
a JSON summary as the last line.  Exits 3 without a TPU.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import loader  # noqa: E402


def readings(cell, seed):
    import numpy as np
    from repro.api import Engine, EngineConfig
    from repro.core.cost_model import choose_tau
    from repro.graph.structs import Graph
    e, tr = cell.config["engine"], cell.traffic
    n, src, dst = cell.generator.generate(cell.config, seed)
    g = Graph(n, src, dst)
    eng = Engine(EngineConfig(backend=e["backend"], layout=e["layout"],
                              balance=e["balance"], devices=cell.chips,
                              use_mirroring=e["mirroring"]))
    pg = eng.partition(g, e["workers"],
                       tau=choose_tau(g.out_degrees(), e["workers"]),
                       seed=harness.sub_seed(seed))
    res = eng.run(tr["algo"], pg, **tr["params"])
    got = cell.ref.from_program(np.asarray(res.state), pg.perm)
    ref = cell.ref.reference(n, src, dst, tr["params"])
    program = cell.ref.compare(got, ref)
    if tr["algo"] == "sv":
        early = eng.run("sv", pg,
                        max_supersteps=int(res.n_supersteps) - 2)
        ctl = cell.ref.from_program(np.asarray(early.state), pg.perm)
    else:
        ctl = cell.ref.control(n, src, dst, tr["params"])
    return {"seed": seed, "supersteps": int(res.n_supersteps),
            "program": program, "control": cell.ref.compare(ctl, ref)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = loader.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CHECKOUT
                                                  / ".jax_cache")
    sys.path.insert(0, str(harness.CHECKOUT / "src"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        harness.log("JAX found no TPU")
        return 3
    rows = []
    for seed in args.seeds:
        t = time.perf_counter()
        r = readings(cell, seed)
        rows.append(r)
        harness.log(f"seed {seed}: {r['supersteps']} supersteps, program "
                    f"{r['program']}, control {r['control']} "
                    f"({time.perf_counter() - t:.1f}s)")
    keys = sorted(rows[0]["program"])
    print(json.dumps({
        "workload": cell.name, "limits": cell.traffic["limits"],
        "lower": {k: max(r["program"][k] for r in rows) for k in keys},
        "control_min": {k: min(r["control"][k] for r in rows)
                        for k in keys},
        "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

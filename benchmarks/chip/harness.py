"""One run of one benchmark cell on the chip.

Set-up makes the cell's graph from ``--seed`` with the benchmark's own
generator, partitions it through ``Engine.partition`` (the program's
partitioner layer) and runs one warm-up job, which compiles the job's
programs or reads them from JAX's persistent cache in the checkout's
``.jax_cache``.  The window then runs the cell's job back to back: each
job is ``Engine.run(algo, pg, **params)`` on the partitioned graph, ending
in ``np.asarray(state)``, and the window stops after the first job that
ends past ``--seconds``.  ``Engine.run`` rebuilds its shards and plans and
retraces on every call; users pay that on every call, so it stays inside
the timed job.

Once the window has closed and the device's peak memory has been read,
every job's answer is compared with the cell's plain NumPy reference on
the same graph, in original vertex order.  Each number compared and its
limit are printed as the last lines of standard error and, under
``checks``, as the last key of the result line, which is the last line of
standard output.

The timed metric is ``superstep_s``: the window's seconds over the
supersteps of all its jobs.  A PageRank job always runs its fixed
iterations, but S-V's superstep count follows the seed's hashed ids, so
a time per job would spread with the seed where a time per superstep
does not.  Each job's seconds and supersteps are logged and reported
under ``jobs``.

``--trace 1`` runs the same window under the JAX profiler and reports
the cell's per-layer metrics (the readers under ``metrics/``), the
device's busy and window seconds and a breakdown, instead of the
end-to-end metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import loader
import tracereduce as tracelib
from peaks import peaks_for

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileWatch:
    """Counts persistent-cache requests and hits from ``jax.monitoring``
    (a request that misses is a compilation) and sums the backend's
    compile seconds, cache reads included."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        self.compile_s = 0.0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/compile_requests_use_cache":
                self.requests += 1
            elif name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def compiles(self) -> int:
        return self.requests - self.hits


def sub_seed(seed: int) -> int:
    """A 32-bit seed for the partitioner's RandomState, from any seed."""
    import numpy as np
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def run_cell(cell: loader.Cell, seed: int, seconds: float, trace: bool,
             t0: float, peaks: dict | None, watch: CompileWatch | None = None,
             readers: dict | None = None) -> dict:
    """Set-up, window and comparison of one run; returns the result line
    as a dict (``checks`` last)."""
    import jax
    import numpy as np
    from repro.api import Engine, EngineConfig
    from repro.core.cost_model import choose_tau
    from repro.graph.structs import Graph

    ann = jax.profiler.TraceAnnotation
    ecfg, tr = cell.config["engine"], cell.traffic
    algo, params = tr["algo"], tr["params"]

    n, src, dst = cell.generator.generate(cell.config, seed)
    g = Graph(n, src, dst)
    eng = Engine(EngineConfig(backend=ecfg["backend"], layout=ecfg["layout"],
                              balance=ecfg["balance"], devices=cell.chips,
                              use_mirroring=ecfg["mirroring"]))
    M = int(ecfg["workers"])
    tau = choose_tau(g.out_degrees(), M)
    tp = time.perf_counter()
    pg = eng.partition(g, M, tau=tau, seed=sub_seed(seed))
    partition_s = time.perf_counter() - tp
    log(f"{cell.name} seed={seed}: n={n} m={g.m} M={M} tau={pg.tau}; "
        f"generate+partition {tp - t0:.3f}+{partition_s:.3f}s")

    def job():
        tj = time.perf_counter()
        with ann("bench.engine_run"):
            res = eng.run(algo, pg, **params)
        with ann("bench.to_host"):
            state = np.asarray(res.state)
        return {"out": cell.ref.from_program(state, pg.perm),
                "stats": res.stats, "supersteps": int(res.n_supersteps),
                "seconds": time.perf_counter() - tj}

    tw = time.perf_counter()
    warm = job()
    log(f"warm-up job: {warm['supersteps']} supersteps, "
        f"{time.perf_counter() - tw:.3f}s; set-up so far: "
        + (f"{watch.compiles()} compilations, {watch.hits} cache hits, "
           f"backend compile {watch.compile_s:.3f}s" if watch else ""))

    tdir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir.name, profiler_options=opts)
    compiles0 = watch.compiles() if watch else 0
    jobs = []
    t_w0 = time.perf_counter()
    setup_s = t_w0 - t0
    with ann(tracelib.WINDOW):
        while True:
            with ann("bench.job"):
                jobs.append(job())
            if time.perf_counter() - t_w0 >= seconds:
                break
    window_s = time.perf_counter() - t_w0
    window_compiles = (watch.compiles() - compiles0) if watch else 0
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = tracelib.reduce(tracelib.load_xplane(tdir.name))
        tdir.cleanup()
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    steps = sum(j["supersteps"] for j in jobs)
    log(f"window: {len(jobs)} jobs, {steps} supersteps in {window_s:.3f}s, "
        f"{window_compiles} compilations, peak {peak} bytes; jobs "
        + ", ".join(f"{j['seconds']:.3f}s/{j['supersteps']}" for j in jobs))

    # the comparison, after the window and the memory reading
    del pg, eng
    ref = cell.ref.reference(n, src, dst, params)
    limits = tr["limits"]
    worst = {k: None for k in limits}
    failed = 0
    for j in jobs:
        nums = cell.ref.compare(j["out"], ref)
        if any(not nums[k] <= limits[k] for k in limits):
            failed += 1
        for k in limits:
            if worst[k] is None or not nums[k] <= worst[k]:
                worst[k] = nums[k]
    checks = {k: {"value": worst[k], "limit": limits[k]} for k in limits}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(jobs) and failed == 0, "attempted": len(jobs),
           "failed": failed}
    if trace:
        rec = {"partition_s": partition_s, "jobs": jobs,
               "counter": tr["counter"], "trace": reduced, "peaks": peaks,
               "graph": {"n": n, "m": int(g.m),
                         "n_recv": int(np.count_nonzero(
                             np.bincount(dst, minlength=n)))}}
        metrics = {}
        for name, mod in sorted((readers or {}).items()):
            v = mod.read(rec)
            if v is not None:
                metrics[name] = {"value": v, "unit": mod.UNIT}
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out.update(metrics=metrics, device=device,
                   breakdown={"device_ops": reduced["device_ops"],
                              "idle_gaps": reduced["idle_gaps"]})
    else:
        out.update(metrics={
            "superstep_s": {"value": window_s / steps, "unit": "s"},
            "hbm_peak_gb": {"value": peak / 1e9, "unit": "GB"},
            "setup_s": {"value": setup_s, "unit": "s"}}, device=device)
    out["window_compiles"] = window_compiles
    out["jobs"] = [[j["seconds"], j["supersteps"]] for j in jobs]
    out["checks"] = checks
    return out


def main(argv, t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (CHECKOUT / "src" / "repro").is_dir():
        log(f"no program: {CHECKOUT / 'src' / 'repro'} is missing")
        return 2
    try:
        cell = loader.load_cell(args.workload)
    except FileNotFoundError as e:
        log(str(e))
        return 2
    cache = CHECKOUT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"JAX found no TPU (platform {devs[0].platform!r})")
        return 3
    if len(devs) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, {len(devs)} visible")
        return 3
    try:
        peaks = peaks_for(devs[0].device_kind)
    except KeyError as e:
        log(str(e))
        return 3
    watch = CompileWatch(jax)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0,
                      peaks, watch,
                      loader.metric_readers() if args.trace else None)
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0

#!/usr/bin/env python3
"""Smoke run of the BSP engine and the resident graph service on a TPU.

    python3 chip_smoke.py              # one chip: phases A and B
    python3 chip_smoke.py --chips 4    # four chips: phase A, devices=4 vs 1

Phase A drives ``Engine(EngineConfig(backend="pallas", layout="csr",
devices=D))`` on a power-law graph (``powerlaw(n, avg_deg=16,
seed=0).symmetrized()``, M=32 workers, tau from ``choose_tau``): Hash-Min
labels must equal a NumPy union-find's components, 30 PageRank supersteps
must match a NumPy power iteration with the engine's dangling rule (rank
held by vertices without out-edges is dropped) within rtol 1e-5, and the
lowered programs of the run must hold the compiled Pallas kernel
(``tpu_custom_call``).  With ``--chips 4`` phase A runs at devices=4 and
devices=1 in this one process and holds them to the conformance contract:
bitwise-equal labels, integer-exact ``msgs_*`` / ``per_worker_*`` stats,
PageRank within tolerance.

The configuration's graph has n=2**22 (m ~ 1.3e8 directed edges); the
smoke runs n=2**21 by default because a cold n=2**22 run took 1,023 s on
one TPU v5e, too close to the 1,200 s a smoke may take (host graph build
and ~6 s per superstep dominate).  ``--n 4194304`` runs the full size.

Phase B (one chip) boots a ``GraphService`` with the default csr /
edge-balanced / dense config on ``launch/serve_graph``'s default graph
(weighted power-law, n=200,000, avg_deg 8), warms one 16-query bucket,
answers a mixed batch of 16 SSSP / PPR / ego queries, folds a 1 %
edge-churn delta and re-answers.  The trace counter must stay flat, and
the answers must match SciPy Dijkstra (SSSP), a NumPy power iteration
(PPR) and the NumPy union-find (ego) on each epoch's graph.  Its size is
the demo's, not phase A's: the dense path holds about 1.2 KB of (edge,
query) temporaries per edge at 16 queries, so phase A's graph would need
~150 GB of HBM.

Earlier lines report sizes, set-up / compile / per-phase wall times
(smoke timings, not benchmark numbers), message counts, the correctness
results and each device's peak HBM bytes.  The last line is the JSON
verdict.  The script exits non-zero, printing no verdict, when JAX finds
no TPU, when the repository's ``src/`` is not beside it, or when any
phase fails.  The compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``.jax_cache`` of the checkout.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
IR_DIR = ROOT / ".smoke_ir"
PR_RTOL = 1e-5
WORKERS = 32        # M, the partition's worker count
PR_ITERS = 30       # PageRank supersteps
N_FULL = 2 ** 22    # the configuration's vertex count
N_SMOKE = 2 ** 21   # default: the full size runs too close to the limit


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


# ---------------------------------------------------------------------------
# plain NumPy / SciPy references, independent of the engine
# ---------------------------------------------------------------------------

def components_ref(n, src, dst):
    """(n,) min original id of each vertex's component: union-find that
    hooks the larger root under the smaller and compresses every path to
    its root each round (parents only decrease, so no cycles form)."""
    import numpy as np
    parent = np.arange(n, dtype=np.int64)
    s, d = src[src < dst], dst[src < dst]
    while True:
        ps, pd = parent[s], parent[d]
        live = ps != pd
        if not live.any():
            return parent
        s, d, ps, pd = s[live], d[live], ps[live], pd[live]
        np.minimum.at(parent, np.maximum(ps, pd), np.minimum(ps, pd))
        while True:
            up = parent[parent]
            if np.array_equal(up, parent):
                break
            parent = up


def pagerank_ref(n, src, dst, iters, damping=0.85):
    """Power iteration with the engine's dangling rule."""
    import numpy as np
    deg = np.bincount(src, minlength=n)
    pr = np.full(n, 1.0 / n)
    for _ in range(iters):
        contrib = np.where(deg > 0, pr / np.maximum(deg, 1), 0.0)
        pr = (1 - damping) / n + damping * np.bincount(
            dst, weights=contrib[src], minlength=n)
    return pr


def ppr_ref(n, src, dst, sources, alpha, iters):
    """(n, len(sources)) personalized PageRank, restart at each source."""
    import numpy as np
    deg = np.bincount(src, minlength=n)[:, None]
    restart = np.zeros((n, len(sources)))
    restart[sources, np.arange(len(sources))] = 1.0
    pr = restart.copy()
    for _ in range(iters):
        contrib = np.where(deg > 0, pr / np.maximum(deg, 1), 0.0)
        inbox = np.stack([np.bincount(dst, weights=contrib[src, j],
                                      minlength=n)
                          for j in range(len(sources))], axis=1)
        pr = alpha * restart + (1 - alpha) * inbox
    return pr


def sssp_ref(n, src, dst, w, sources):
    """(n, len(sources)) shortest distances (SciPy Dijkstra); parallel
    edges keep their lightest weight."""
    import numpy as np
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import dijkstra
    key = src.astype(np.int64) * n + dst
    order = np.lexsort((w, key))
    first = np.ones(len(order), bool)
    first[1:] = key[order][1:] != key[order][:-1]
    o = order[first]
    a = csr_matrix((w[o].astype(np.float64), (src[o], dst[o])), shape=(n, n))
    return dijkstra(a, indices=np.asarray(sources)).T


# ---------------------------------------------------------------------------
# phase A: Engine.run on the pallas / csr path
# ---------------------------------------------------------------------------

def lowered_has_kernel(fn):
    """Run ``fn`` while JAX dumps every lowered module; True when one of
    them holds the compiled Pallas kernel."""
    import jax
    shutil.rmtree(IR_DIR, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", str(IR_DIR))
    try:
        out = fn()
    finally:
        jax.config.update("jax_dump_ir_to", "")
    found = any("tpu_custom_call" in p.read_text(errors="ignore")
                for p in IR_DIR.rglob("*.mlir"))
    shutil.rmtree(IR_DIR, ignore_errors=True)
    return out, found


def phase_a(pg, devices, check_kernel=True):
    """Hash-Min + PageRank through Engine.run; returns the raw results."""
    import numpy as np
    from repro.api import Engine, EngineConfig
    eng = Engine(EngineConfig(backend="pallas", layout="csr",
                              devices=devices))
    out = {}
    for algo, params in (("hashmin", {}),
                         ("pagerank", {"n_iters": PR_ITERS, "tol": 0.0})):
        t0 = time.perf_counter()
        run = lambda: eng.run(algo, pg, **params)  # noqa: E731
        if check_kernel:
            res, kernel = lowered_has_kernel(run)
        else:
            res, kernel = run(), None
        state = np.asarray(res.state)
        dt = time.perf_counter() - t0
        out[algo] = {"state": state, "stats": res.stats,
                     "n_supersteps": int(res.n_supersteps), "wall_s": dt,
                     "kernel": kernel}
        msgs = {k: int(v) for k, v in res.stats.items()
                if k.startswith("msgs_")}
        log(f"phase A devices={devices} {algo}: {out[algo]['n_supersteps']} "
            f"supersteps, wall {dt:.3f}s (incl. trace+compile), {msgs}")
    return out


def check_phase_a(pg, g, out):
    """Hash-Min vs union-find, PageRank vs the NumPy power iteration."""
    import numpy as np
    from repro.graph.structs import canonical_labels
    errors = []
    t0 = time.perf_counter()
    want = components_ref(g.n, g.src, g.dst)
    got = canonical_labels(pg, out["hashmin"]["state"])
    n_bad = int((got != want).sum())
    log(f"hashmin vs NumPy union-find: {len(np.unique(want))} components, "
        f"{n_bad} mismatched vertices")
    if n_bad:
        errors.append(f"hashmin: {n_bad} vertices off the reference")
    pr_it = out["pagerank"]["n_supersteps"]
    if pr_it != PR_ITERS:
        errors.append(f"pagerank ran {pr_it} supersteps, not {PR_ITERS}")
    ref = pagerank_ref(g.n, g.src, g.dst, pr_it)
    pr = out["pagerank"]["state"].reshape(-1)[pg.perm].astype(np.float64)
    rel = float(np.max(np.abs(pr - ref) / np.abs(ref)))
    log(f"pagerank vs NumPy power iteration ({pr_it} iters): max rel err "
        f"{rel!r} (limit {PR_RTOL}); refs took "
        f"{time.perf_counter() - t0:.3f}s")
    if not rel <= PR_RTOL:
        errors.append(f"pagerank max rel err {rel!r} > {PR_RTOL}")
    for algo in ("hashmin", "pagerank"):
        if out[algo]["kernel"] is False:
            errors.append(f"{algo}: no tpu_custom_call in the lowered run")
    return errors


def check_conformance(a, b):
    """devices=D vs devices=1: bitwise labels, exact stats, PR tolerance."""
    import numpy as np
    errors = []
    labels_eq = np.array_equal(a["hashmin"]["state"], b["hashmin"]["state"])
    if not labels_eq:
        errors.append("hashmin labels differ between device counts")
    for algo in ("hashmin", "pagerank"):
        sa, sb = a[algo]["stats"], b[algo]["stats"]
        bad = sorted(set(sa) ^ set(sb)) + [
            k for k in sa if k in sb and not np.array_equal(
                np.asarray(sa[k]), np.asarray(sb[k]))]
        if bad:
            errors.append(f"{algo}: stats differ on {bad}")
    pa, pb = a["pagerank"]["state"], b["pagerank"]["state"]
    mask = pb != 0
    rel = float(np.max(np.abs(pa - pb)[mask] / np.abs(pb[mask])))
    if not rel <= PR_RTOL:
        errors.append(f"pagerank devices differ by rel {rel!r}")
    log(f"conformance vs devices=1: hashmin labels bitwise equal "
        f"{labels_eq}, pagerank max rel diff {rel!r}, "
        f"{'OK' if not errors else errors}")
    return errors


# ---------------------------------------------------------------------------
# phase B: the resident graph service
# ---------------------------------------------------------------------------

def _check_answers(svc, g, results):
    """Every answer of one epoch against the references on its graph."""
    import numpy as np
    errors = []
    by_kind = {k: [r for r in results if r.query.kind == k]
               for k in ("sssp", "ppr", "ego")}
    if by_kind["sssp"]:
        srcs = [r.query.source for r in by_kind["sssp"]]
        want = sssp_ref(g.n, g.src, g.dst, g.weight, srcs)
        for j, r in enumerate(by_kind["sssp"]):
            if not np.allclose(r.value, want[:, j], rtol=1e-5,
                               equal_nan=True):
                errors.append(f"sssp from {r.query.source} off")
    if by_kind["ppr"]:
        srcs = [r.query.source for r in by_kind["ppr"]]
        want = ppr_ref(g.n, g.src, g.dst, srcs, svc.ppr_alpha,
                       svc.ppr_iters)
        for j, r in enumerate(by_kind["ppr"]):
            if not np.allclose(r.value, want[:, j], rtol=1e-5, atol=1e-7):
                errors.append(f"ppr from {r.query.source} off")
    if by_kind["ego"]:
        root = components_ref(g.n, g.src, g.dst)
        size = np.bincount(root, minlength=g.n)
        for r in by_kind["ego"]:
            v = r.query.source
            if tuple(r.value) != (int(root[v]), int(size[root[v]])):
                errors.append(f"ego of {v} off")
    return errors


def phase_b(n, avg_deg, workers, seed, churn, batch=16):
    from repro.api import EngineConfig
    from repro.core.service import GraphClient, GraphService
    from repro.graph import generators
    from repro.launch.serve_graph import _churn_delta, _mixed_batch
    errors = []
    t0 = time.perf_counter()
    g = generators.powerlaw(n, avg_deg=avg_deg, seed=seed,
                            weighted=True).symmetrized()
    cfg = EngineConfig(layout="csr", balance="edges", devices=1)
    svc = GraphService(g, M=workers, config=cfg, buckets=(batch,),
                       seed=seed)
    client = GraphClient(svc)
    log(f"phase B graph n={g.n} m={g.m} M={workers} tau={svc.pg.tau}: "
        f"set-up {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    svc.warmup()
    warm = svc.traces
    log(f"phase B warmup: {warm} traces in {time.perf_counter() - t0:.3f}s")
    queries = _mixed_batch(g.n, batch, seed)
    t0 = time.perf_counter()
    first = client.request(queries)
    dt1 = time.perf_counter() - t0
    delta = _churn_delta(g, churn, seed)
    svc.mutate(delta)
    t0 = time.perf_counter()
    post = client.request(queries)
    dt2 = time.perf_counter() - t0
    lp = svc.last_pump
    log(f"phase B: {len(first)} queries in {dt1:.3f}s; folded "
        f"{len(delta.rem_src)} removals + {len(delta.add_src)} adds and "
        f"re-answered {len(post)} in {dt2:.3f}s ({lp['n_supersteps']} "
        f"supersteps, epoch {svc.epoch}, traces {svc.traces - warm} "
        f"after warmup)")
    if svc.traces != warm:
        errors.append(f"service re-traced {svc.traces - warm} times")
    if svc.epoch != 1 or any(r.epoch != 1 for r in post):
        errors.append("post-fold answers not all from epoch 1")
    t0 = time.perf_counter()
    errors += _check_answers(svc, g, first)
    errors += _check_answers(svc, svc.snapshot_graph(), post)
    log(f"phase B answers vs references (both epochs): "
        f"{'OK' if not errors else errors} in "
        f"{time.perf_counter() - t0:.3f}s")
    return errors


# ---------------------------------------------------------------------------

def watch_device(jax, devs):
    """Returns ``report(phase)``: logs the backend compile seconds (cache
    reads included) and persistent-cache hits since the last report, and
    each device's HBM high-water mark so far."""
    tot = {"compile_s": 0.0, "cache_hits": 0}
    seen = dict(tot)

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            tot["compile_s"] += secs

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            tot["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    def report(phase):
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devs]
        log(f"{phase}: compile {tot['compile_s'] - seen['compile_s']:.3f}s, "
            f"persistent cache hits {tot['cache_hits'] - seen['cache_hits']},"
            f" peak_bytes_in_use so far {peaks}")
        seen.update(tot)
    return report


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=N_SMOKE)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} but {len(devs)} devices are visible")
    devs = devs[:args.chips]
    report = watch_device(jax, devs)
    log(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{cache_dir}")
    if args.n == N_SMOKE:
        log(f"phase A graph cut from n={N_FULL} to n={N_SMOKE}: the full "
            f"size ran 1,023 s cold on one v5e, near the 1,200 s smoke "
            f"limit")
    elif args.n != N_FULL:
        log(f"phase A graph cut from n={N_FULL} to n={args.n} by --n")

    from repro.core.cost_model import choose_tau
    from repro.graph import generators
    from repro.graph.structs import partition
    t0 = time.perf_counter()
    g = generators.powerlaw(args.n, avg_deg=16,
                            seed=args.seed).symmetrized()
    tau = choose_tau(g.out_degrees(), WORKERS)
    pg = partition(g, WORKERS, tau=tau, seed=args.seed, layout="csr")
    log(f"phase A graph n={g.n} m={g.m} M={WORKERS} tau={tau} "
        f"max_deg={int(g.out_degrees().max())}: set-up (generate + "
        f"partition) {time.perf_counter() - t0:.3f}s")
    report("set-up")

    errors = []
    if args.chips == 1:
        out = phase_a(pg, 1)
        report("phase A")
        errors += check_phase_a(pg, g, out)
        del out, pg, g
        from repro.launch.serve_graph import build_parser
        sg = build_parser().parse_args([])
        errors += phase_b(sg.n, sg.avg_deg, sg.workers, args.seed, sg.churn)
        report("phase B")
    else:
        out4 = phase_a(pg, args.chips)
        report(f"phase A devices={args.chips}")
        errors += check_phase_a(pg, g, out4)
        out1 = phase_a(pg, 1, check_kernel=False)
        report("phase A devices=1")
        errors += check_conformance(out4, out1)
    if errors:
        fail("; ".join(errors))
    log("OK")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

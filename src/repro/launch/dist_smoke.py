"""Two-process ``jax.distributed`` localhost smoke for the hierarchical
(host, device) mesh.

Launches itself ``--hosts`` times (default 2) as real OS processes, each
calling ``jax.distributed.initialize`` against a localhost coordinator
with ``--per-host`` forced CPU devices, then runs one sharded hashmin on
the 2-D ``(hosts, per_host)`` mesh and compares against the
single-process reference.  This is the launch path a real multi-host
deployment uses (process h owns mesh row h; ``launch/mesh.py`` maps
worker block ``[h*T, (h+1)*T)`` onto it).

jaxlib's CPU backend cannot *execute* multi-process computations (no
cross-process CPU collective transport in this build: execution fails
with ``Multiprocess computations aren't implemented on the CPU
backend``), so on CPU-only machines the smoke verifies the coordinator
handshake + global device enumeration and then SKIPS the execution leg
with exit code 3 — a skip is never reported as a pass.  On a real
multi-host accelerator fleet the same entrypoint runs the full parity
check.

    PYTHONPATH=src python -m repro.launch.dist_smoke

Exit codes: 0 = parity OK; 3 = execution leg skipped (CPU backend);
1 = real failure (handshake broke, wrong device counts, or parity
violated).
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

_CPU_UNSUPPORTED = "Multiprocess computations aren't implemented"
SKIP = 3


def _worker(rank: int, hosts: int, per_host: int, port: int, n: int,
            M: int) -> int:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={per_host} "
        + os.environ.get("XLA_FLAGS", ""))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.distributed.initialize(f"localhost:{port}", num_processes=hosts,
                               process_id=rank)
    n_local = len(jax.local_devices())
    n_global = len(jax.devices())
    print(f"[dist_smoke] rank {rank}: {n_local} local / {n_global} global "
          f"devices", flush=True)
    if n_local != per_host or n_global != hosts * per_host:
        print(f"[dist_smoke] rank {rank}: device enumeration wrong "
              f"(want {per_host}/{hosts * per_host})", flush=True)
        return 1

    import numpy as np
    from repro.algorithms.hashmin import hashmin
    from repro.graph import generators as gen
    from repro.graph.structs import partition

    g = gen.powerlaw(n, avg_deg=5, seed=1, weighted=True).symmetrized()
    pg = partition(g, M, tau=8, seed=0, layout="csr", hosts=hosts)
    ref, ref_stats, _ = hashmin(pg, backend="pallas")
    try:
        lab, stats, _ = hashmin(pg, backend="pallas",
                                devices=(hosts, per_host))
    except Exception as e:  # noqa: BLE001 — classify, don't mask
        if _CPU_UNSUPPORTED in str(e):
            print(f"[dist_smoke] rank {rank}: SKIP execution — this "
                  f"jaxlib cannot run multi-process computations on the "
                  f"CPU backend (handshake + enumeration verified)",
                  flush=True)
            return SKIP
        raise
    ok = (np.array_equal(np.asarray(lab), np.asarray(ref))
          and all(np.array_equal(np.asarray(stats[k]),
                                 np.asarray(ref_stats[k]))
                  for k in ref_stats))
    print(f"[dist_smoke] rank {rank}: parity "
          + ("OK" if ok else "VIOLATED"), flush=True)
    return 0 if ok else 1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hosts", type=int, default=2)
    ap.add_argument("--per-host", type=int, default=2)
    ap.add_argument("--port", type=int, default=12421)
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--timeout", type=int, default=600)
    ap.add_argument("--rank", type=int, default=None,
                    help=argparse.SUPPRESS)  # internal: worker re-exec
    args = ap.parse_args()

    if args.rank is not None:
        sys.exit(_worker(args.rank, args.hosts, args.per_host, args.port,
                         args.n, args.workers))

    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "repro.launch.dist_smoke",
             "--rank", str(r), "--hosts", str(args.hosts),
             "--per-host", str(args.per_host), "--port", str(args.port),
             "--n", str(args.n), "--workers", str(args.workers)],
            env=dict(os.environ))
        for r in range(args.hosts)]
    codes = []
    for p in procs:
        try:
            codes.append(p.wait(timeout=args.timeout))
        except subprocess.TimeoutExpired:
            p.kill()
            codes.append(124)
    print(f"[dist_smoke] worker exit codes: {codes}")
    if all(c == 0 for c in codes):
        sys.exit(0)
    sys.exit(SKIP if all(c in (0, SKIP) for c in codes) else 1)


if __name__ == "__main__":
    main()

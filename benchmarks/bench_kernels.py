"""Kernel microbenchmarks: Pallas (interpret) vs jnp reference, plus the
jnp-path timing that is the CPU-meaningful number.  Interpret-mode wall time
is NOT TPU performance — the TPU claim is the VMEM/BlockSpec structure
checked here for fit, and the roofline table in EXPERIMENTS.md."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timed
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.segment_combine.ops import (pack_edges, pack_values,
                                               segment_combine)
from repro.kernels.ssd_scan.ops import ssd_scan

VMEM_BUDGET = 16 * 2 ** 20  # v5e ~16MB/core usable


def _vmem_report():
    print("# kernel VMEM working sets (bytes, must be << 16MiB)")
    eb, nb = 512, 256
    seg = (eb * nb + eb + nb) * 4
    bq = bk = 512
    d = 256
    fla = (bq * d + 2 * bk * d + bq * bk + 2 * bq + bq * d) * 4
    q, p, n = 128, 64, 128
    ssd = (q * (p + 2 * n + 1) + q * q + p * n * 2 + q * p) * 4
    for name, b in [("segment_combine", seg), ("flash_attention", fla),
                    ("ssd_scan", ssd)]:
        assert b < VMEM_BUDGET, (name, b)
        print(f"vmem.{name},{b},fits=True")


def _bench_channel_backends():
    """Dense vmap-scatter vs plan-driven combine on one broadcast step —
    the tentpole comparison (same inbox, same stats, different memory)."""
    from repro.core.channels import broadcast
    from repro.core import plan as planlib
    from repro.graph import generators as gen
    from repro.graph.structs import partition

    g = gen.powerlaw(40_000, avg_deg=8, seed=0, alpha=1.8).symmetrized()
    M = 16
    pg = partition(g, M, tau=60, seed=0)
    vals = jnp.where(pg.vmask, 1.0, 0.0)
    results = {}
    for backend in ("dense", "pallas"):
        fn = jax.jit(lambda v: broadcast(pg, v, pg.vmask, op="min",
                                         backend=backend)[0])
        fn(vals).block_until_ready()
        _, secs = timed(lambda: fn(vals).block_until_ready(), repeat=3)
        results[backend] = secs
        row(f"chan.broadcast.{backend}.n40k", secs,
            f"M={M};E={g.m}")
    plan = planlib.get_plan(pg, "eg")
    dense_bytes = M * pg.n_pad * 4
    row("chan.broadcast.mem", 0.0,
        f"dense_partial_bytes={dense_bytes};"
        f"plan_packed_bytes={plan.packed_bytes};"
        f"speed_ratio={results['dense'] / max(results['pallas'], 1e-9):.2f}")


def _bench_vector_feature_sweep():
    """(lanes, F) feature-blocked combine: pallas (interpret) vs jnp ref
    across payload widths.  Timings are INTERLEAVED best-of — variant A
    and B alternate within each round (single-core container: never run
    the contenders concurrently, and let clock drift hit both alike)."""
    import time

    from repro.kernels.segment_combine.kernel import segment_combine_blocks
    from repro.kernels.segment_combine.ref import segment_combine_blocks_ref

    rng = np.random.RandomState(1)
    nb, eb, n_blocks = 256, 512, 8
    idx = jnp.asarray(rng.randint(-1, nb, (n_blocks, eb)).astype(np.int32))
    for F in (1, 8, 32, 128):
        vals = jnp.asarray(rng.randn(n_blocks, eb, F).astype(np.float32))
        fk = jax.jit(lambda v, i: segment_combine_blocks(v, i, "sum", nb))
        fr = jax.jit(
            lambda v, i: segment_combine_blocks_ref(v, i, "sum", nb))
        fk(vals, idx).block_until_ready()
        fr(vals, idx).block_until_ready()
        best_k = best_r = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fk(vals, idx).block_until_ready()
            best_k = min(best_k, time.perf_counter() - t0)
            t0 = time.perf_counter()
            fr(vals, idx).block_until_ready()
            best_r = min(best_r, time.perf_counter() - t0)
        lanes = n_blocks * eb
        row(f"kern.segment_combine.vec.F{F}.pallas", best_k,
            f"lanes={lanes};nb={nb}")
        row(f"kern.segment_combine.vec.F{F}.ref_jnp", best_r,
            f"pallas_over_ref={best_k / max(best_r, 1e-9):.2f}")


def run():
    _vmem_report()
    rng = np.random.RandomState(0)

    # segment_combine: graph-scale message combining
    E, N = 200_000, 16_384
    dst = rng.randint(0, N, E)
    vals = rng.randn(E).astype(np.float32)
    (order, idxl), pack_secs = timed(pack_edges, dst, N, nb=256, repeat=3)
    row("kern.pack_edges.vectorized.E200k", pack_secs, f"E={E};N={N}")
    pv = jnp.asarray(pack_values(vals, order, idxl, "sum"))
    idxl = jnp.asarray(idxl)
    f_ref = jax.jit(lambda v, i: segment_combine(v, i, "sum", 256, N,
                                                 use_kernel=False))
    f_ref(pv, idxl).block_until_ready()
    _, secs = timed(lambda: f_ref(pv, idxl).block_until_ready(), repeat=3)
    row("kern.segment_combine.ref_jnp.E200k", secs, f"E={E};N={N}")

    # feature-blocked (lanes, F) payload sweep
    _bench_vector_feature_sweep()

    # channel-layer backend comparison (dense scatters vs message plans)
    _bench_channel_backends()

    # flash attention (jnp ref path = CPU-meaningful; kernel checked in tests)
    B, S, H, K, hd = 1, 1024, 8, 2, 64
    q = jnp.asarray(rng.randn(B, S, H, hd), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, K, hd), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, K, hd), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, use_kernel=False))
    f(q, k, v).block_until_ready()
    _, secs = timed(lambda: f(q, k, v).block_until_ready(), repeat=3)
    flops = 4 * B * S * S * H * hd / 2
    row("kern.flash_attention.ref_jnp.S1024", secs,
        f"gflops_s={flops / secs / 1e9:.1f}")

    # ssd scan
    b, s, h, p, n = 1, 2048, 8, 64, 64
    x = jnp.asarray(rng.randn(b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(b, s, h), jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.randn(h), jnp.float32) * 0.3)
    Bm = jnp.asarray(rng.randn(b, s, 1, n), jnp.float32)
    Cm = jnp.asarray(rng.randn(b, s, 1, n), jnp.float32)
    f = jax.jit(lambda *a: ssd_scan(*a, chunk=128, use_kernel=False))
    f(x, dt, A, Bm, Cm).block_until_ready()
    _, secs = timed(lambda: f(x, dt, A, Bm, Cm).block_until_ready(),
                    repeat=3)
    row("kern.ssd_scan.ref_jnp.S2048", secs, f"bhpn={b}x{h}x{p}x{n}")
    return True


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()

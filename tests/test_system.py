"""End-to-end system behaviour tests."""
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest


def test_graph_driver_end_to_end(capsys):
    sys.argv = ["graph_run", "--algo", "hashmin", "--graph", "powerlaw",
                "--n", "2000", "--workers", "8", "--tau", "auto"]
    from repro.launch.graph_run import main
    main()
    out = capsys.readouterr().out
    assert "supersteps" in out and "msgs_total" in out


def test_serve_driver_end_to_end():
    from repro.launch.serve import run
    toks = run("tinyllama_1_1b", True, batch=2, prompt_len=8, gen=4)
    assert toks.shape == (2, 4)


def test_train_driver_loss_decreases(tmp_path):
    from repro.launch.train import run
    losses = run("tinyllama_1_1b", True, steps=30, batch=4, seq=32,
                 ckpt_dir=str(tmp_path), ckpt_every=0, lr=3e-3,
                 log_every=100)
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])
    assert np.isfinite(losses).all()


def test_graph_engine_lowers_on_mesh():
    """The BSP superstep compiles SPMD over a worker mesh: the worker-axis
    transposes become all-to-alls (the multi-pod-readiness proof at test
    scale; launch/dryrun.py is the 512-device version)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.graph import generators as gen
        from repro.graph.structs import partition
        from repro.core.channels import broadcast
        g = gen.powerlaw(4000, avg_deg=6, seed=0).symmetrized()
        pg = partition(g, 8, tau=32, seed=0)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("w",))
        sh = NamedSharding(mesh, P("w"))
        def superstep(vals, active):
            return broadcast(pg, vals, active, op="min", use_mirroring=True)
        vals = jax.device_put(jnp.where(pg.vmask, 1.0, jnp.inf), sh)
        act = jax.device_put(pg.vmask, sh)
        lowered = jax.jit(superstep, in_shardings=(sh, sh)).lower(vals, act)
        compiled = lowered.compile()
        txt = compiled.as_text()
        has_coll = any(k in txt for k in
                       ("all-to-all", "all-reduce", "all-gather",
                        "collective-permute"))
        assert has_coll, "expected collectives in SPMD graph engine"
        inbox, stats = jax.jit(superstep, in_shardings=(sh, sh))(vals, act)
        assert bool(jnp.isfinite(stats["msgs_total"] * 1.0))
        print("OK collectives present")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=".", timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_bsp_run_halts_and_accumulates():
    from repro.core import bsp

    def step(state, i):
        state = state + 1.0
        stats = {"x": jnp.ones(()), "v": jnp.ones((3,))}
        return state, state >= 5.0, stats

    final, stats, n, hist = bsp.run(step, jnp.zeros(()), 100)
    assert hist is None
    assert float(final) == 5.0 and int(n) == 5
    assert float(stats["x"]) == 5.0
    np.testing.assert_array_equal(np.asarray(stats["v"]), 5 * np.ones(3))


def test_bsp_history():
    from repro.core import bsp

    def step(state, i):
        return state + 1.0, state >= 2.0, {"m": state}

    final, stats, n, hist = bsp.run(step, jnp.zeros(()), 10,
                                    record_history=True)
    assert int(n) == 3
    np.testing.assert_allclose(np.asarray(hist["m"])[:3], [0.0, 1.0, 2.0])

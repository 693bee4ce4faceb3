"""The comparison that decides ``correct``, and its control, at tiny
sizes on the CPU with the Pallas kernel interpreted.

The program's answers stay within each cell's limit, and the control
fails it: for PageRank the reference computed one precision step below
the configuration's float32 (bfloat16), for S-V the program's own job
stopped before its last superstep that moves a label (the superstep
after it only confirms the halt): a stale answer where the
configuration states exact components.  Put in the program's place
under the harness, the control turns ``correct`` false.  ``control.py``
runs the same readings on the chip at the cells' own sizes."""
import time

import numpy as np
import pytest

import harness
import loader

SEEDS = (2**31 + 3, 17, 90210)


@pytest.fixture
def pallas_kernel():
    from repro.core import plan
    plan.set_kernel_mode("pallas")
    yield
    plan.set_kernel_mode("auto")


def _run(cell, seed):
    return harness.run_cell(cell, seed, 0.0, False, time.perf_counter(),
                            None)


@pytest.mark.parametrize("seed", SEEDS)
def test_pagerank_program_passes_and_control_fails(bench_root, seed,
                                                   pallas_kernel,
                                                   monkeypatch):
    from repro.api import Engine
    cell = loader.load_cell("pagerank.tiny-kron", bench_root)
    res = _run(cell, seed)
    assert res["correct"], res["checks"]
    limit = cell.traffic["limits"]["pr_max_rel_err"]
    assert res["checks"]["pr_max_rel_err"]["value"] < limit / 3

    n, src, dst = cell.generator.generate(cell.config, seed)
    ctl = cell.ref.control(n, src, dst, cell.traffic["params"])
    real = Engine.run

    def control_run(self, algo, pg, **params):
        res = real(self, algo, pg, **params)
        state = np.array(res.state)
        state.reshape(-1)[pg.perm] = ctl
        res.state = state
        return res
    monkeypatch.setattr(Engine, "run", control_run)
    res = _run(cell, seed)
    assert not res["correct"] and res["failed"] == res["attempted"]
    assert res["checks"]["pr_max_rel_err"]["value"] > 3 * limit


@pytest.mark.parametrize("seed", SEEDS)
def test_sv_program_passes_and_early_stop_fails(bench_root, seed,
                                                pallas_kernel, monkeypatch):
    from repro.api import Engine
    cell = loader.load_cell("sv.tiny-road", bench_root)
    res = _run(cell, seed)
    assert res["correct"], res["checks"]
    real = Engine.run

    def early_run(self, algo, pg, **params):
        full = real(self, algo, pg, **params)
        return real(self, algo, pg,
                    max_supersteps=int(full.n_supersteps) - 2)
    monkeypatch.setattr(Engine, "run", early_run)
    res = _run(cell, seed)
    assert not res["correct"] and res["failed"] == res["attempted"]
    assert res["checks"]["sv_wrong_vertices"]["value"] > 0

"""A whole run on the CPU (the harness's look for a chip skipped) with the
timed path broken underneath: ``correct`` has to come out false for
every fault a one-chip cell can have.  (The exchange between chips is
left out of these: the cells run on one chip, where there is none.)"""
import time

import numpy as np
import pytest

import harness
import loader

CELLS = ("pagerank.tiny-kron", "sv.tiny-road")
SEED = 2**31 + 29


def _run(bench_root, name):
    cell = loader.load_cell(name, bench_root)
    return harness.run_cell(cell, SEED, 0.0, False, time.perf_counter(),
                            None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(bench_root, name):
    res = _run(bench_root, name)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_step_returning_its_state_unchanged(bench_root, name, monkeypatch):
    from repro.core import bsp
    real = bsp.run

    def frozen_run(step, state, *a, **k):
        def frozen(st, i):
            _, halted, stats = step(st, i)
            return st, halted, stats
        return real(frozen, state, *a, **k)
    monkeypatch.setattr(bsp, "run", frozen_run)
    assert not _run(bench_root, name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_edges_left_out(bench_root, name, monkeypatch):
    from repro.api import Engine
    from repro.graph.structs import Graph, partition
    real = Engine.run

    def half_run(self, algo, pg, **params):
        inv = pg.inv_perm
        s = inv[np.asarray(pg.all_src)]
        d = inv[np.asarray(pg.all_dst)]
        keep = (s < d) & ((s + d) % 2 == 0)
        g = Graph(pg.n, np.concatenate([s[keep], d[keep]]),
                  np.concatenate([d[keep], s[keep]]))
        half = partition(g, pg.M, tau=pg.tau, layout=pg.layout,
                         perm=pg.perm)
        return real(self, algo, half, **params)
    monkeypatch.setattr(Engine, "run", half_run)
    assert not _run(bench_root, name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_one_answer_altered(bench_root, name, monkeypatch):
    from repro.api import Engine
    real = Engine.run

    def altered_run(self, algo, pg, **params):
        res = real(self, algo, pg, **params)
        state = np.array(res.state)
        v = int(np.argmax(np.asarray(pg.vmask).reshape(-1)
                          & (np.arange(state.size) > 0)))
        flat = state.reshape(-1)
        if algo == "pagerank":
            flat[v] *= 1.001
        else:
            flat[v] = v      # its own id: not the component's minimum
        res.state = state
        return res
    monkeypatch.setattr(Engine, "run", altered_run)
    assert not _run(bench_root, name)["correct"]

"""The sharded executor's plan cache: each partitioned graph's stacked
device plans are built once per device layout and reused, read-only, by
every later ``Engine.run`` on it."""
import os
import sys

import jax
import numpy as np
import pytest

from repro.api import Engine, EngineConfig
from repro.core import exec as exec_mod
from repro.core import plan as planlib
from repro.core import spans
from repro.graph import generators as gen
from repro.graph.structs import EdgeDelta, fold_delta

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks", "chip"))
import tracereduce  # noqa: E402

ALGOS = {"pagerank": {"n_iters": 3, "tol": 0.0}, "sv": {}}
KINDS = {"pagerank": exec_mod.broadcast_plan_kinds("pallas", True),
         "sv": exec_mod.broadcast_plan_kinds("pallas", False)}


def _graph():
    return gen.powerlaw(240, avg_deg=5, seed=4, weighted=True).symmetrized()


def _engine():
    return Engine(EngineConfig(backend="pallas", layout="csr", devices=1))


def _partition(eng, g):
    return eng.partition(g, 8, tau=8, seed=2)


def _uncached(pg, D, kind, chunks=None, hier=None):
    return exec_mod._stack_plans(
        exec_mod._device_plans(pg, D, kind, planlib.default_nb()),
        pg.M // D, chunks=chunks, hier=hier)


@pytest.fixture
def builds(monkeypatch):
    """Counts real plan builds: calls of ``exec._device_plans``."""
    calls = []
    real = exec_mod._device_plans

    def counting(pg, D, kind, nb):
        calls.append(kind)
        return real(pg, D, kind, nb)

    monkeypatch.setattr(exec_mod, "_device_plans", counting)
    return calls


def _assert_same_plan(got, want):
    (gmeta, garrs), (wmeta, warrs) = got, want
    assert gmeta == wmeta
    assert set(garrs) == set(warrs)
    for k, v in warrs.items():
        assert garrs[k].dtype == v.dtype and np.array_equal(garrs[k], v), k


@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_second_run_builds_no_plan_and_matches_a_fresh_graph(algo, builds):
    eng, g = _engine(), _graph()
    pg = _partition(eng, g)
    eng.run(algo, pg, **ALGOS[algo])
    assert sorted(builds) == sorted(KINDS[algo])
    cached = {k: v[1] for k, v in pg.plan_cache.items() if k[0] == "sharded"}
    assert len(cached) == len(KINDS[algo])
    again = eng.run(algo, pg, **ALGOS[algo])
    assert len(builds) == len(KINDS[algo])
    assert all(pg.plan_cache[k][1] is v for k, v in cached.items())

    fresh = eng.run(algo, _partition(eng, g), **ALGOS[algo])
    assert len(builds) == 2 * len(KINDS[algo])
    assert np.array_equal(np.asarray(again.state), np.asarray(fresh.state))
    assert again.n_supersteps == fresh.n_supersteps
    assert set(again.stats) == set(fresh.stats)
    for k, v in fresh.stats.items():
        assert np.array_equal(np.asarray(again.stats[k]), np.asarray(v)), k


def _plan_of(meta, arrays, kind):
    pre = f"plan_{kind}_"
    return (meta["plan_meta"][kind],
            {k[len(pre):]: v for k, v in arrays.items() if k.startswith(pre)})


@pytest.mark.parametrize("devices,pipeline,pipeline_chunks", [
    (1, False, None), (2, False, None), (1, True, None), (2, True, None),
    (2, True, 3), ((2, 2), False, None)])
def test_each_device_layout_is_cached_apart_and_equals_an_uncached_build(
        devices, pipeline, pipeline_chunks):
    pg = _partition(_engine(), _graph())
    exec_mod._shard_graph(pg, 1, ("eg",))
    D, hier = exec_mod._normalize_devices(devices)
    for _ in range(2):                               # the miss, the hit
        meta, arrays, _ = exec_mod._shard_graph(
            pg, devices, ("eg",), pipeline, pipeline_chunks)
        chunks = meta["pipeline_chunks"] if pipeline else None
        _assert_same_plan(_plan_of(meta, arrays, "eg"),
                          _uncached(pg, D, "eg", chunks, hier))
    keys = [k for k in pg.plan_cache if k[0] == "sharded"]
    assert len(keys) == (1 if (devices, pipeline) == (1, False) else 2)


def test_pipeline_and_device_count_are_separate_keys():
    pg = _partition(_engine(), _graph())
    for devices, pipeline in ((1, False), (2, False), (1, True),
                              (2, True)):
        exec_mod._shard_graph(pg, devices, ("eg",), pipeline)
    keys = sorted((k[2], k[5] or 0) for k in pg.plan_cache
                  if k[0] == "sharded")
    # the pipeline defaults to one chunk on one device, two on a mesh
    assert keys == [(1, 0), (1, 1), (2, 0), (2, 2)]


def test_a_folded_graph_starts_with_an_empty_cache():
    eng, g = _engine(), _graph()
    pg = _partition(eng, g)
    eng.run("pagerank", pg, **ALGOS["pagerank"])
    assert any(k[0] == "sharded" for k in pg.plan_cache)
    folded = fold_delta(pg, EdgeDelta(add_src=np.array([0, 1]),
                                      add_dst=np.array([2, 3]),
                                      add_w=np.ones(2, np.float32)))
    assert folded.plan_cache == {}
    assert folded.plan_cache is not pg.plan_cache


def test_cached_plan_arrays_are_read_only():
    pg = _partition(_engine(), _graph())
    meta, arrays, _ = exec_mod._shard_graph(pg, 1, ("eg", "mir"))
    for kind in ("eg", "mir"):
        row_gather = arrays[f"plan_{kind}_row_gather"]
        with pytest.raises(ValueError):
            row_gather[0, 0, 0] = 7
    # callers get their own containers: editing them leaves the cache be
    meta["plan_meta"]["eg"]["n_rows"] = -1
    arrays["plan_eg_row_gather"] = None
    meta2, arrays2, _ = exec_mod._shard_graph(pg, 1, ("eg",))
    assert meta2["plan_meta"]["eg"]["n_rows"] > 0
    assert arrays2["plan_eg_row_gather"] is not None


def test_plan_span_wraps_the_lookup_on_a_hit_too(tmp_path, builds):
    pg = _partition(_engine(), _graph())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
            exec_mod._shard_graph(pg, 1, ("eg",))    # miss: builds
            exec_mod._shard_graph(pg, 1, ("eg",))    # hit
    finally:
        jax.profiler.stop_trace()
    assert builds == ["eg"]
    host = tracereduce.load_xplane(str(tmp_path))["host"]
    plans = [e for e in host if e[0] == spans.PLAN]
    shards = sorted((e for e in host if e[0] == spans.SHARD_GRAPH),
                    key=lambda e: e[1])
    assert len(plans) == 2 and len(shards) == 2
    for shard in shards:                 # one exec.plan in each call
        assert sum(shard[1] <= p[1] and p[1] + p[2] <= shard[1] + shard[2]
                   for p in plans) == 1

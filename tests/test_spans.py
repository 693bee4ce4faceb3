"""The program's profiler spans and device scopes (``core/spans.py``):
host spans nest on the thread that calls ``Engine.run``, and the device
scopes reach the lowered program's op metadata."""
import os
import sys

import jax
import numpy as np
import pytest

from repro.api import Engine, EngineConfig
from repro.core import exec as exec_mod
from repro.core import spans
from repro.graph.structs import Graph

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks", "chip"))
import scopereduce  # noqa: E402
import tracereduce  # noqa: E402

ALGOS = {"pagerank": {"n_iters": 2, "tol": 0.0}, "sv": {}}


def _partitioned():
    rng = np.random.default_rng(3)
    n = 300
    src, dst = rng.integers(0, n, 1500), rng.integers(0, n, 1500)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    g = Graph(n, np.concatenate([src, dst]), np.concatenate([dst, src]))
    eng = Engine(EngineConfig(backend="pallas", layout="csr", devices=1))
    return eng, eng.partition(g, 8, tau=12, seed=1)


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2]


def test_span_tables_agree_with_the_trace_reduction():
    assert set(spans.LAYERS) == set(spans.SPANS) | set(spans.SCOPES)
    assert scopereduce.SPANS == spans.SPANS
    assert scopereduce.SCOPES == spans.SCOPES


@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_host_spans_nest_in_engine_run(algo, tmp_path):
    eng, pg = _partitioned()
    eng.run(algo, pg, **ALGOS[algo])                 # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
            np.asarray(eng.run(algo, pg, **ALGOS[algo]).state)
    finally:
        jax.profiler.stop_trace()
    host = tracereduce.load_xplane(str(tmp_path))["host"]
    by = {name: [e for e in host if e[0] == name] for name in spans.SPANS}
    assert all(len(by[name]) >= 1 for name in spans.SPANS), \
        {k: len(v) for k, v in by.items()}
    (run,) = by[spans.ENGINE_RUN]
    (shard,) = by[spans.SHARD_GRAPH]
    assert _inside(shard, run)
    assert all(_inside(p, shard) for p in by[spans.PLAN])
    (trace,) = by[spans.TRACE]
    (launch,) = by[spans.LAUNCH]
    assert _inside(trace, run) and _inside(launch, run)
    assert launch[1] >= shard[1] + shard[2]
    # JAX's own host events of the call sit inside the launch span
    jax_events = [e for e in host if e[0].startswith("PjitFunction")
                  and _inside(e, run)]
    assert jax_events and any(_inside(e, launch) for e in jax_events)


@pytest.mark.parametrize("algo", sorted(ALGOS))
def test_device_scopes_reach_the_lowered_program(algo, monkeypatch):
    texts = []
    build = exec_mod.build_sharded

    def spy(*args, **kwargs):
        fn, fargs, stats_shape = build(*args, **kwargs)
        texts.append(fn.lower(*fargs).as_text(debug_info=True))
        return fn, fargs, stats_shape

    monkeypatch.setattr(exec_mod, "build_sharded", spy)
    eng, pg = _partitioned()
    eng.run(algo, pg, **ALGOS[algo])
    (text,) = texts
    sup = spans.SUPERSTEP + "/"
    assert sup + spans.COMBINE in text
    assert spans.EXCHANGE in text and spans.STATS in text
    assert (sup + spans.REQRESP in text) == (algo == "sv")

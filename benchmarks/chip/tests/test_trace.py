"""The trace reduction: busy-interval union, idle share, kernel time and
idle gaps charged to host activity."""
import json
from pathlib import Path

import pytest

import loader
import tracereduce as tr

DATA = Path(__file__).resolve().parent / "data"


def _ev(name, start, dur):
    return [name, start, dur]


def _synthetic():
    # window [100, 1100) ns; one op spills in, one out; a while op holds
    # the kernel's call
    dev = [_ev("%fusion.1 = f32[8] fusion(...)", 50, 100),   # 100..150
           _ev("%fusion.2 = f32[8] fusion(...)", 150, 50),
           _ev("%while.1 = (s32[]) while(...)", 380, 140),
           _ev('%body.3 = s32[8,128] custom-call(...), '
               'custom_call_target="tpu_custom_call"', 400, 100),
           _ev("%fusion.1 = f32[8] fusion(...)", 1050, 200)]  # ..1100
    host = [_ev(tr.WINDOW, 100, 1000),
            _ev("bench.job", 100, 1000),
            _ev("bench.engine_run", 150, 600),
            _ev("lower_sharding_computation", 250, 100),
            _ev("bench.to_host", 800, 50)]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_union_and_gaps():
    assert tr.union([(0, 5), (3, 8), (10, 12), (12, 13)]) == \
        [[0, 8], [10, 13]]
    assert tr.gaps([[2, 4], [6, 7]], (0, 10)) == [(0, 2), (4, 6), (7, 10)]


def test_reduce_synthetic():
    r = tr.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,200) + [380,520) + [1050,1100) = 290 ns
    assert r["busy_s"] == pytest.approx(290e-9)
    ops = dict(r["device_ops"])
    assert ops["%fusion.1"] == pytest.approx(100e-9)
    assert ops["%while.1"] == pytest.approx(40e-9)   # self: 140 - 100
    assert ops["%body.3 (tpu_custom_call)"] == pytest.approx(100e-9)
    idle = dict(r["idle_gaps"])
    # idle [200,380): engine_run 200..250, lowering 250..350, engine_run
    # 350..380; idle [520,1050): engine_run 520..750, job 750..800,
    # to_host 800..850, job 850..1050
    assert idle["lower_sharding_computation"] == pytest.approx(100e-9)
    assert idle["bench.engine_run"] == pytest.approx(310e-9)
    assert idle["bench.to_host"] == pytest.approx(50e-9)
    assert idle["bench.job"] == pytest.approx(250e-9)
    assert sum(idle.values()) == pytest.approx(1000e-9 - 290e-9)


def test_readers_on_synthetic():
    r = tr.reduce(_synthetic())
    readers = loader.metric_readers()
    rec = {"trace": r, "peaks": {"hbm_bytes_per_s": 819e9},
           "jobs": [{"supersteps": 5, "stats": {}}],
           "graph": {"m": 10, "n_recv": 5}}
    assert readers["device_idle_pct"].read(rec) == pytest.approx(71.0)
    assert readers["superstep_device_ms"].read(rec) == \
        pytest.approx(290e-9 * 1e3 / 5)
    # 5 supersteps x (8 * 10 + 4 * 5) B over 819 GB/s, in 100 ns
    want = 100 * 5 * 100 / 819e9 / 100e-9
    assert readers["combine_roofline"].read(rec) == pytest.approx(want)
    rec["trace"] = None
    assert all(readers[k].read(rec) is None for k in
               ("device_idle_pct", "superstep_device_ms",
                "combine_roofline"))


def test_recorded_chip_trace():
    """300 ms of a PageRank run's trace on a TPU v5e (g500-s18): the host
    building and lowering the next job, then its device loop starting,
    up to the first call of the combine kernel."""
    events = json.loads((DATA / "trace_pagerank_g500-s18.json").read_text())
    r = tr.reduce(events)
    a, b = tr.window_of(events["host"])
    assert r["window_s"] == pytest.approx((b - a) / 1e9)
    # busy by brute force: a microsecond grid over the window
    import numpy as np
    grid = np.zeros((b - a) // 1000 + 1, bool)
    for _, s, d in events["device"]["/device:TPU:0"]:
        grid[(s - a) // 1000:(s + d - a) // 1000] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, abs=2e-5)
    assert r["busy_s"] == pytest.approx(0.059692469)
    assert sum(v for _, v in r["idle_gaps"]) <= r["window_s"] - r["busy_s"]
    idle = dict(r["idle_gaps"])
    assert max(idle, key=idle.get) == "lower_sharding_computation"
    ops = dict(r["device_ops"])
    assert "%body.27 (tpu_custom_call)" in ops
    kernel = [ev for _, _, _, ev in r["ops"]
              if loader.metric_readers()["combine_roofline"].is_kernel(ev)]
    assert len(kernel) == 1

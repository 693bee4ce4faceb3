"""The program's host spans and device scopes in the trace reduction
(``scopereduce``) and the per-layer metrics that read them."""
import hashlib
import json
from pathlib import Path

import pytest

import loader
import scopereduce as sr
import tracereduce as tr

DATA = Path(__file__).resolve().parent / "data"
# sha256 of the keys ``reduce`` gives on the recorded g500-s18 trace
# without the scope readers loaded: loading them must not move those keys
RECORDED_DIGEST = \
    "ea732a6c5f80093cb6682de6cd673c3fc64c24c3bf886541d0b5666a94e5a11c"
OLD_KEYS = ("window_s", "busy_s", "n_devices", "device_ops", "idle_gaps",
            "ops")
SCOPE_READERS = {"combine_device_ms": "ch.combine",
                 "exchange_device_ms": "ch.exchange",
                 "reqresp_device_ms": "ch.reqresp",
                 "stats_device_ms": "ch.stats",
                 "apply_device_ms": "bsp.superstep"}
SPAN_READERS = ("shard_build_s", "plan_build_s", "launch_s")


def _digest(r):
    got = json.dumps({k: r[k] for k in OLD_KEYS}, sort_keys=True)
    return hashlib.sha256(got.encode()).hexdigest()


def _synthetic():
    # window [100, 1100) ns; the program's spans on the host
    ev = lambda name, s, d: [name, s, d]  # noqa: E731
    host = [ev(tr.WINDOW, 100, 1000), ev("bench.job", 100, 1000),
            ev("engine.run", 150, 600), ev("exec.shard_graph", 160, 200),
            ev("exec.plan", 170, 50), ev("exec.plan", 230, 60),
            ev("exec.trace", 400, 40), ev("exec.launch", 450, 90),
            ev("exec.launch", 1000, 300)]                   # cut at 1100
    dev = [ev("%fusion.1 = f32[8] fusion(...)", 380, 140)]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_reduce_keeps_its_keys_on_the_recorded_trace():
    events = json.loads((DATA / "trace_pagerank_g500-s18.json").read_text())
    loader.metric_readers()                 # the readers install the wrap
    assert getattr(tr.reduce, "scopereduce", False)
    r = tr.reduce(events)
    assert _digest(r) == RECORDED_DIGEST
    assert r["spans"] == [] and r["scopes"] == {}
    assert set(sr.scope_times(r)) == {sr.NO_SCOPE}
    sr.install()                            # a second install is a no-op
    assert _digest(tr.reduce(events)) == RECORDED_DIGEST


@pytest.mark.parametrize("path,scope", [
    ("", sr.NO_SCOPE),
    ("jit(inner)/while/body/bsp.superstep/mul:", "bsp.superstep"),
    ("jit(inner)/while/body/bsp.superstep/ch.combine/ch.stats/"
     "segment_combine/pallas_call:", "ch.stats"),
    ("jit(inner)/while/body/ch.stats/add:", "ch.stats"),
    # a name that only contains a scope's name is not the scope
    ("jit(inner)/my.ch.combine/add:", sr.NO_SCOPE),
])
def test_scope_of(path, scope):
    assert sr.scope_of(path) == scope


def test_scope_times_synthetic():
    sup = "jit(inner)/while/body/bsp.superstep/"
    dev = [("%while.1", 0, 1000, ""),                       # loop: outside
           ("%fusion.1", 10, 100, sup + "mul:"),            # apply
           ("%fusion.2", 120, 200, sup + "ch.combine/gather:"),
           ("%sort_fusion.3", 330, 50, ""),                 # after combine
           ("%while.4", 400, 300, sup + "ch.exchange/while:"),
           ("%copy.5", 410, 40, ""),                        # first in loop
           ("%fusion.6", 460, 100, sup + "ch.exchange/while/body/add:"),
           ("%fusion.7", 720, 80, "jit(inner)/while/body/ch.stats/add:"),
           ("%copy.8", 1100, 50, "")]                      # top level
    plane = "/device:TPU:0"
    ops = [(plane, s, s + d, [name, s, d]) for name, s, d, _ in dev]
    scopes = {plane: {name: path for name, _, _, path in dev if path}}
    t = sr.scope_times({"ops": ops, "scopes": scopes})
    assert t == {sr.NO_SCOPE: 1000 - 100 - 200 - 50 - 300 - 80 + 50,
                 "bsp.superstep": 100, "ch.combine": 250,
                 "ch.exchange": 300, "ch.stats": 80}
    busy = sum(e - s for s, e in tr.union([o[1:] for o in ops]))
    assert sum(t.values()) == busy
    # two devices add up
    ops2 = ops + [("/device:TPU:1",) + o[1:] for o in ops]
    scopes2 = {**scopes, "/device:TPU:1": scopes[plane]}
    assert sr.scope_times({"ops": ops2, "scopes": scopes2}) == \
        {k: 2 * v for k, v in t.items()}


def test_span_readers_on_synthetic():
    readers = loader.metric_readers()
    r = tr.reduce(_synthetic())
    assert [s[0] for s in r["spans"]] == [
        "engine.run", "exec.shard_graph", "exec.plan", "exec.plan",
        "exec.trace", "exec.launch", "exec.launch"]
    assert r["spans"][-1] == ["exec.launch", 1000, 100]
    rec = {"trace": r, "jobs": [{"supersteps": 5, "stats": {}}] * 2}
    assert readers["shard_build_s"].read(rec) == pytest.approx(90e-9 / 2)
    assert readers["plan_build_s"].read(rec) == pytest.approx(110e-9 / 2)
    assert readers["launch_s"].read(rec) == pytest.approx(230e-9 / 2)


@pytest.mark.parametrize("name", [*SPAN_READERS, *SCOPE_READERS])
def test_new_readers_are_silent_without_spans_or_scopes(name):
    """A program without spans or scopes, as the parent of this change
    is: each new reader gives None and does not raise."""
    reader = loader.metric_readers()[name]
    ev = _synthetic()
    ev["host"] = [h for h in ev["host"] if h[0] not in sr.SPANS]
    jobs = [{"supersteps": 5, "stats": {}}]
    assert reader.read({"trace": tr.reduce(ev), "jobs": jobs}) is None
    assert reader.read({"trace": None, "jobs": jobs}) is None


def test_op_scopes_reads_the_wire_format():
    """A hand-built ``XSpace``: one device plane whose op metadata carries
    the scope stat by string value and by reference, one host plane."""
    def varint(v):
        out = b""
        while v >= 0x80:
            out, v = out + bytes([v & 0x7F | 0x80]), v >> 7
        return out + bytes([v])

    def ld(field, payload):
        return varint(field << 3 | 2) + varint(len(payload)) + payload

    def vi(field, v):
        return varint(field << 3) + varint(v)

    def stat_meta(i, name):            # map entry (id -> XStatMetadata)
        return ld(5, vi(1, i) + ld(2, vi(1, i) + ld(2, name.encode())))

    def op_meta(i, name, stat):        # map entry (id -> XEventMetadata)
        return ld(4, vi(1, i) + ld(2, vi(1, i) + ld(2, name.encode())
                                   + ld(5, stat)))

    dev = (ld(2, b"/device:TPU:0") + stat_meta(1, "other")
           + stat_meta(2, sr.SCOPE_STAT) + stat_meta(3, "a/ch.stats/add:")
           + op_meta(1, "%fusion.1", vi(1, 2) + ld(5, b"x/ch.combine/g:"))
           + op_meta(2, "%fusion.2", vi(1, 2) + vi(7, 3))
           + op_meta(3, "%copy.3", vi(1, 1) + ld(5, b"ignored")))
    host = ld(2, b"/host:CPU") + stat_meta(2, sr.SCOPE_STAT)
    got = sr.op_scopes(ld(1, dev) + ld(1, host))
    assert got == {"/device:TPU:0": {"%fusion.1": "x/ch.combine/g:",
                                     "%fusion.2": "a/ch.stats/add:"}}


@pytest.mark.parametrize("algo", ["pagerank", "sv"])
def test_recorded_scope_trace(algo):
    """A TPU v5e trace of the instrumented program (PageRank at scale 14,
    S-V on a 96 x 96 lattice; one chip, 32 workers): the window runs from
    ``engine.run``'s start until a few hundred device ops have started."""
    events = json.loads(
        (DATA / "trace_scopes_small.json").read_text())[algo]
    readers = loader.metric_readers()
    r = tr.reduce(events)
    rec = {"trace": r, "jobs": [{"supersteps": 1, "stats": {}}],
           "peaks": {"hbm_bytes_per_s": 819e9}, "graph": {"m": 1, "n_recv": 1}}
    # the five scopes and the ops outside them add up to the busy time
    times = sr.scope_times(r)
    assert set(times) <= set(sr.SCOPES) | {sr.NO_SCOPE}
    assert sum(times.values()) == pytest.approx(r["busy_s"] * 1e9, rel=1e-2)
    busy_ms = readers["superstep_device_ms"].read(rec)
    scoped = {k: readers[k].read(rec) for k in SCOPE_READERS}
    outside = times[sr.NO_SCOPE] / 1e6
    assert sum(v or 0 for v in scoped.values()) + outside == \
        pytest.approx(busy_ms, rel=1e-2)
    assert scoped["combine_device_ms"] > 0 and scoped["stats_device_ms"] > 0
    assert (scoped["reqresp_device_ms"] is not None) == (algo == "sv")
    # the host spans: one of each, nested in engine.run, read per job
    spans = {s[0]: [] for s in r["spans"]}
    for name, s, d in r["spans"]:
        spans[name].append((s, s + d))
    assert set(spans) == set(sr.SPANS)
    (run,) = spans["engine.run"]
    (shard,) = spans["exec.shard_graph"]
    assert run[0] <= shard[0] and shard[1] <= run[1]
    assert all(shard[0] <= a and b <= shard[1] for a, b in spans["exec.plan"])
    plan = sum(b - a for a, b in spans["exec.plan"])
    assert readers["plan_build_s"].read(rec) == pytest.approx(plan / 1e9)
    assert readers["shard_build_s"].read(rec) == \
        pytest.approx((shard[1] - shard[0] - plan) / 1e9)
    launch = sum(b - a for k in ("exec.trace", "exec.launch")
                 for a, b in spans[k])
    assert readers["launch_s"].read(rec) == pytest.approx(launch / 1e9)
    # the idle gaps fall to the program's spans and JAX's own events
    idle = dict(r["idle_gaps"])
    assert not {"bench.engine_run", "bench.job"} & set(idle)
    # the kernel keeps its custom-call marker under its new name
    kernel = [ev for _, _, _, ev in r["ops"]
              if readers["combine_roofline"].is_kernel(ev)]
    assert kernel and all(ev[0].startswith("%segment_combine")
                          for ev in kernel)

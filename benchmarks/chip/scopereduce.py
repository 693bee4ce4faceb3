"""The program's host spans and device scopes, read from the same trace
as ``tracereduce``.

A device op's scope path is the ``op_name`` metadata of its HLO op
(``jit(inner)/while/body/bsp.superstep/ch.combine/gather``), which holds
the program's ``jax.named_scope`` names.  ``ProfileData`` does not expose
it, so ``op_scopes`` reads it off the ``.xplane.pb`` wire format.

``install()`` wraps ``tracereduce.load_xplane`` and ``tracereduce.reduce``
in place, so that the harness's own calls also return, beside every key
they returned before and unchanged:

* from ``load_xplane``, ``scopes``: device plane -> {op event name:
  scope path};
* from ``reduce``, ``spans``: the program's host spans (``SPANS``) of
  the window, each ``[name, start_ns, dur_ns]`` cut to the window, and
  ``scopes`` passed on.

The per-layer readers of spans and scopes call ``install()`` when they
are loaded, which the harness does before it traces.  ``scope_times``
charges each device op's self time to the innermost of the program's
device scopes (``SCOPES``) in its path.  Both name lists are the
program's ``repro.core.spans`` tables, repeated here so that a program
that has neither reads as no span and no scope.
"""
from __future__ import annotations

import collections
import glob
import os
import sys

import tracereduce

# the scope-path stat of a device op event
SCOPE_STAT = "tf_op"
SPANS = ("engine.run", "exec.shard_graph", "exec.plan", "exec.trace",
         "exec.launch")
SCOPES = ("bsp.superstep", "ch.combine", "ch.exchange", "ch.reqresp",
          "ch.stats")
NO_SCOPE = ""


def install() -> None:
    """Wrap ``tracereduce``'s loader and reduction (once)."""
    if getattr(tracereduce.reduce, "scopereduce", False):
        return
    base_load, base_reduce = tracereduce.load_xplane, tracereduce.reduce

    def load_xplane(log_dir: str) -> dict:
        out = base_load(log_dir)
        out["scopes"] = {}
        paths = sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        try:
            with open(paths[-1], "rb") as f:
                out["scopes"] = op_scopes(f.read())
        except (ValueError, IndexError, UnicodeDecodeError) as e:
            # the old keys still hold; the scope readers then read None
            print(f"[scopereduce] no scope paths: {e!r}", file=sys.stderr)
        return out

    def reduce(events: dict, top: int = 10) -> dict:
        out = base_reduce(events, top)
        window = tracereduce.window_of(events["host"])
        out["spans"] = [[ev[0], s, e - s] for s, e, ev
                        in tracereduce.clip(events["host"], window)
                        if ev[0] in SPANS]
        out["scopes"] = events.get("scopes", {})
        return out

    reduce.scopereduce = True
    tracereduce.load_xplane, tracereduce.reduce = load_xplane, reduce


def op_scopes(xspace: bytes) -> dict:
    """Device plane name -> {op name: scope path} from a serialized
    ``XSpace``: the ``SCOPE_STAT`` stat of each op's event metadata.  The
    few message fields needed are read off the protobuf wire format
    (XSpace.planes 1; XPlane name 2, event_metadata 4, stat_metadata 5;
    map entry key 1, value 2; XEventMetadata name 2, stats 5;
    XStatMetadata id 1, name 2; XStat metadata_id 1, str_value 5,
    ref_value 7)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        parts = collections.defaultdict(list)
        for g, v in _fields(plane):
            parts[g].append(v)
        name = bytes(parts[2][0]).decode() if parts[2] else ""
        if not tracereduce.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for entry in parts[5]:
            meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
            stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        ids = [k for k, v in stat_names.items() if v == SCOPE_STAT]
        paths = {}
        for entry in parts[4]:
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            op = next((bytes(v).decode() for g, v in meta if g == 2), "")
            for g, v in meta:
                stat = dict(_fields(v)) if g == 5 else {}
                if ids and stat.get(1) == ids[0]:
                    paths[op] = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
        out[name] = paths
    return out


def _fields(buf):
    """``(field number, value)`` of each field of a protobuf message: an
    int for a varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def scope_of(path: str) -> str:
    """The innermost of ``SCOPES`` in a scope path, or ``NO_SCOPE``."""
    for part in reversed(path.rstrip(":").split("/")):
        if part in SCOPES:
            return part
    return NO_SCOPE


def scope_times(trace: dict) -> collections.Counter:
    """Device self ns of ``reduce``'s ``ops``, summed over the planes, by
    the innermost program scope of each op (``NO_SCOPE`` outside them).
    Where ops nest only whole, the values add up to the busy time.

    Ops the compiler made without ``op_name`` metadata (sort fusions,
    copies, some loop ops) have an empty path; such an op takes the scope
    of the op before it in its enclosing op (the loop body it runs in),
    or the enclosing op's own scope if it comes first there."""
    scopes = trace.get("scopes") or {}
    planes = collections.defaultdict(list)
    for p, s, e, ev in trace["ops"]:
        planes[p].append((s, e, scopes.get(p, {}).get(ev[0], "")))
    out = collections.Counter()
    for clipped in planes.values():
        top = [NO_SCOPE]        # scope of the last top-level op
        stack = []              # [end, scope, scope of its last child]
        for s, e, path in sorted(clipped, key=lambda t: (t[0], -t[1])):
            while stack and stack[-1][0] <= s:
                stack.pop()
            last = stack[-1][2] if stack else top
            if stack:
                out[stack[-1][1]] -= min(e, stack[-1][0]) - s
            sc = scope_of(path) if path else last[0]
            last[0] = sc
            out[sc] += e - s
            stack.append((e, sc, [sc]))
    return out


def scope_ms(rec: dict, scope: str):
    """Device self ms per superstep charged to ``scope``, mean over the
    devices; None without a trace, supersteps or an op in the scope."""
    t = rec["trace"]
    steps = sum(j["supersteps"] for j in rec["jobs"])
    if t is None or not steps:
        return None
    times = scope_times(t)
    if scope not in times:
        return None
    return times[scope] / 1e6 / t["n_devices"] / steps


def span_s(rec: dict, *names: str):
    """Host seconds per window job summed over the spans ``names``; None
    without a trace, jobs or such a span."""
    spans = (rec["trace"] or {}).get("spans") or []
    d = [d for name, _, d in spans if name in names]
    if not d or not rec["jobs"]:
        return None
    return sum(d) / 1e9 / len(rec["jobs"])

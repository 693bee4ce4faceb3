"""Reduction of a profiler trace to the benchmark's device numbers.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event lists; everything else works on those lists, so the tests can
check the arithmetic on a small recorded trace without a chip.

An event is ``[name, start_ns, dur_ns]``.  On a TPU an op's name is its
HLO instruction as text (``%fusion.12 = f32[...] fusion(...), ...``), so
a custom call carries its ``custom_call_target``.  The lists are:

* ``device``: plane name -> the ops that ran on that device (the "XLA
  Ops" line of each ``/device:TPU:<i>`` plane; a ``while`` op and the
  ops of its body both appear there, nested in time);
* ``host``: the events of the host thread that drives JAX: the line of
  the ``/host:CPU`` plane that holds the benchmark's ``bench.window``
  annotation, with JAX's own host events beside the ``bench.*`` ones.

Busy time is the union of a device's op intervals, so nested or
overlapping ops count once; idle share is one minus busy over the traced
window.  The top ops are ranked by self time (an op's time less that of
the ops nested in it).  Each idle gap is charged to what the host was
doing in it: the innermost host event that covers each part of the
gap.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
NO_HOST_EVENT = "(no host event)"


def load_xplane(log_dir: str) -> dict:
    """Events of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name) and line.name == OPS_LINE:
                out["device"].setdefault(plane.name, []).extend(
                    _event(e) for e in line.events)
            elif plane.name == HOST_PLANE:
                evs = [_event(e) for e in line.events]
                if any(e[0] == WINDOW for e in evs):
                    out["host"] = evs
    return out


def _event(e) -> list:
    return [e.name, int(e.start_ns), int(e.duration_ns)]


def op_name(name: str) -> str:
    """``%fusion.12`` of an HLO instruction's text; a custom call keeps
    its target: ``%body.3 (tpu_custom_call)``."""
    short = name.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{short} ({m.group(1)})" if m else short


def window_of(host: list) -> tuple:
    """(start_ns, end_ns) of the benchmark's traced window annotation."""
    spans = [(s, s + d) for name, s, d in host if name == WINDOW]
    if not spans:
        raise ValueError(f"no {WINDOW!r} annotation in the host events")
    return spans[0]


def clip(events: list, window: tuple) -> list:
    """``(start, end, event)`` of each event, cut to the window."""
    a, b = window
    out = []
    for ev in events:
        s, e = max(ev[1], a), min(ev[1] + ev[2], b)
        if e > s:
            out.append((s, e, ev))
    return out


def union(intervals) -> list:
    """Merged, sorted ``[start, end)`` intervals."""
    merged = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def gaps(busy: list, window: tuple) -> list:
    """The idle ``[start, end)`` intervals of the window around ``busy``
    (merged intervals inside the window)."""
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def host_activity(host: list, window: tuple):
    """Returns ``charge(a, b) -> {name: ns}``: the interval [a, b) split
    by the innermost host event covering each part of it."""
    evs = sorted(((s, e, ev[0]) for s, e, ev in clip(host, window)),
                 key=lambda t: (t[0], -t[1]))
    cuts = sorted({t for s, e, _ in evs for t in (s, e)})
    # innermost event over each elementary piece [cuts[i], cuts[i+1]):
    # the one that started last among those still open, the shorter or
    # later-listed one on a tie (host events on one thread nest)
    inner = []
    stack, j = [], 0
    for i in range(len(cuts) - 1):
        t = cuts[i]
        while j < len(evs) and evs[j][0] <= t:
            stack.append(evs[j])
            j += 1
        stack = [x for x in stack if x[1] > t]
        inner.append(max(reversed(stack), key=lambda x: (x[0], -x[1]))[2]
                     if stack
                     else NO_HOST_EVENT)

    def charge(a, b):
        out = collections.Counter()
        if not cuts or b <= cuts[0] or a >= cuts[-1]:
            out[NO_HOST_EVENT] += b - a
            return out
        if a < cuts[0]:
            out[NO_HOST_EVENT] += cuts[0] - a
            a = cuts[0]
        if b > cuts[-1]:
            out[NO_HOST_EVENT] += b - cuts[-1]
            b = cuts[-1]
        i = bisect.bisect_right(cuts, a) - 1
        while a < b:
            e = min(cuts[i + 1], b)
            out[inner[i]] += e - a
            a, i = e, i + 1
        return out
    return charge


def self_times(clipped: list) -> collections.Counter:
    """Self ns per op name of one device's clipped ops: each op's time
    less the time of the ops nested in it."""
    out = collections.Counter()
    stack = []
    for s, e, ev in sorted(clipped, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][2]] -= min(e, stack[-1][1]) - s
        name = op_name(ev[0])
        out[name] += e - s
        stack.append((s, e, name))
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """Device numbers of the traced window.

    ``busy_s`` is the mean over the device planes of each one's busy
    union; ``device_ops`` the ``top`` ops by summed self seconds;
    ``idle_gaps`` the ``top`` host activities by the idle seconds charged
    to them (on the first device); ``ops`` keeps every clipped op event
    for the per-layer readers."""
    window = window_of(events["host"])
    planes = sorted(events["device"])
    if not planes:
        raise ValueError("the trace holds no TPU device ops")
    busy_ns, per_op, ops = [], collections.Counter(), []
    for p in planes:
        clipped = clip(events["device"][p], window)
        busy_ns.append(sum(e - s for s, e in union(clipped)))
        per_op.update(self_times(clipped))
        ops.extend((p, s, e, ev) for s, e, ev in clipped)
    first = union(clip(events["device"][planes[0]], window))
    charge = host_activity(events["host"], window)
    idle = collections.Counter()
    for a, b in gaps(first, window):
        idle.update(charge(a, b))
    w_ns = window[1] - window[0]
    return {
        "window_s": w_ns / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "n_devices": len(planes),
        "device_ops": [[k, v / 1e9] for k, v in per_op.most_common(top)],
        "idle_gaps": [[k, v / 1e9] for k, v in idle.most_common(top)],
        "ops": ops,
    }

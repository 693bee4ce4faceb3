"""Superstep loop (``core/bsp.run`` in ``exec.build_sharded``): device
busy milliseconds in the traced window per superstep run there."""
UNIT = "ms"


def read(rec):
    steps = sum(j["supersteps"] for j in rec["jobs"])
    if rec["trace"] is None or not steps:
        return None
    return rec["trace"]["busy_s"] * 1e3 / steps

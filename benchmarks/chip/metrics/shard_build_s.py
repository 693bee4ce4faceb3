"""Shard and plan build (``exec._shard_graph``): host seconds per window
job inside the program's ``exec.shard_graph`` span less its ``exec.plan``
spans: slicing, padding and stacking the shard arrays and the mirror
fetch plan.  None where the trace holds no such span."""
import scopereduce

UNIT = "s"
scopereduce.install()


def read(rec):
    shard = scopereduce.span_s(rec, "exec.shard_graph")
    if shard is None:
        return None
    return shard - (scopereduce.span_s(rec, "exec.plan") or 0.0)

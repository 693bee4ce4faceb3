"""Compile and launch: host seconds per window job inside the program's
``exec.trace`` span (tracing the step for its stats shape) and
``exec.launch`` span (the jitted call: JAX's trace, lowering,
persistent-cache read, upload of the shard arrays and enqueue).  None
where the trace holds neither span."""
import scopereduce

UNIT = "s"
scopereduce.install()


def read(rec):
    return scopereduce.span_s(rec, "exec.trace", "exec.launch")

"""Shard and plan build: host seconds per window job inside the
program's ``exec.plan`` spans, the edge-plan build of each plan kind
(``exec._device_plans`` and ``_stack_plans``, the work in
``plan._pack_edge_plan``).  None where the trace holds no such span."""
import scopereduce

UNIT = "s"
scopereduce.install()


def read(rec):
    return scopereduce.span_s(rec, "exec.plan")

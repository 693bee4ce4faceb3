"""Accounting: device self milliseconds per superstep charged to the
program's ``ch.stats`` scope (the exact ``msgs_*`` and ``per_worker_*``
counts and their fold into the loop's totals), mean over devices.  An op
counts once, with its self time, under the innermost program scope in
its path (``scopereduce.scope_times``).  None where no op of the trace
carries the scope."""
import scopereduce

UNIT = "ms"
SCOPE = "ch.stats"
scopereduce.install()


def read(rec):
    return scopereduce.scope_ms(rec, SCOPE)

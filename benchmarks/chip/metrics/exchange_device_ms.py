"""Channels, exchange: device self milliseconds per superstep charged to
the program's ``ch.exchange`` scope (every cross-worker movement: the
plan ``all_to_all``, the routed combines and fetches, the mirror-value
fetch), mean over devices.  An op counts once, with its self time, under
the innermost program scope in its path (``scopereduce.scope_times``).
None where no op of the trace carries the scope."""
import scopereduce

UNIT = "ms"
SCOPE = "ch.exchange"
scopereduce.install()


def read(rec):
    return scopereduce.scope_ms(rec, SCOPE)

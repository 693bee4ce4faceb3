"""Channels, local combine: device self milliseconds per superstep charged
to the program's ``ch.combine`` scope (a push channel's source reads,
row gather and pack, the ``segment_combine`` kernel, the segment and
block scatters, the mirror fan-out and the sorted combines), mean over
devices.  An op counts once, with its self time, under the innermost
program scope in its path (``scopereduce.scope_times``).  None where no
op of the trace carries the scope."""
import scopereduce

UNIT = "ms"
SCOPE = "ch.combine"
scopereduce.install()


def read(rec):
    return scopereduce.scope_ms(rec, SCOPE)

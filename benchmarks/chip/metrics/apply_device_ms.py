"""Superstep loop, apply: device self milliseconds per superstep of the ops
under the program's ``bsp.superstep`` scope that are in no channel or
accounting scope (the algorithm's vertex update and halt vote), mean
over devices.  An op counts once, with its self time, under the
innermost program scope in its path (``scopereduce.scope_times``).  None
where no op of the trace carries the scope."""
import scopereduce

UNIT = "ms"
SCOPE = "bsp.superstep"
scopereduce.install()


def read(rec):
    return scopereduce.scope_ms(rec, SCOPE)

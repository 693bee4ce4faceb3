"""Channels, request-respond (Ch_req): device self milliseconds per
superstep charged to the program's ``ch.reqresp`` scope (request dedup,
answer and collect; the routed trips inside it are ``ch.exchange``),
mean over devices.  An op counts once, with its self time, under the
innermost program scope in its path (``scopereduce.scope_times``).  None
where no op of the trace carries the scope."""
import scopereduce

UNIT = "ms"
SCOPE = "ch.reqresp"
scopereduce.install()


def read(rec):
    return scopereduce.scope_ms(rec, SCOPE)

"""The program's profiler spans and device scopes, and their layers.

``span`` is a host ``jax.profiler.TraceAnnotation``: a no-op check
unless a trace runs.  ``scope`` is ``jax.named_scope``: it exists only
while tracing, lands in each HLO op's ``op_name`` (the scope path of the
op in a device trace) and costs nothing at run time; it also decorates."""
import functools

import jax

ENGINE_RUN, SHARD_GRAPH, PLAN, TRACE, LAUNCH = (
    "engine.run", "exec.shard_graph", "exec.plan", "exec.trace",
    "exec.launch")
SUPERSTEP, COMBINE, EXCHANGE, REQRESP, STATS = (
    "bsp.superstep", "ch.combine", "ch.exchange", "ch.reqresp", "ch.stats")
SPANS = (ENGINE_RUN, SHARD_GRAPH, PLAN, TRACE, LAUNCH)
SCOPES = (SUPERSTEP, COMBINE, EXCHANGE, REQRESP, STATS)
LAYERS = {ENGINE_RUN: "engine", SHARD_GRAPH: "shard and plan build",
          PLAN: "shard and plan build", TRACE: "compile and launch",
          LAUNCH: "compile and launch", SUPERSTEP: "superstep loop: apply",
          COMBINE: "channels: local combine", EXCHANGE: "channels: exchange",
          REQRESP: "channels: request-respond", STATS: "accounting"}
span = jax.profiler.TraceAnnotation
scope = jax.named_scope


def traced(name: str):
    """Decorator: run the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap

"""Named host spans on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace runs it
lands, with its stats, in the same ``.xplane.pb`` as the device's operations,
so every span shares the device trace's clock. With no trace running it costs
one to three microseconds on a CPU host. There is no switch of its own:
starting the profiler turns the spans on. A stat that only a trace needs and
that costs more than an attribute read is computed only when ``tracing()`` is
true, and added with ``set_metadata``.

``SPANS`` lists every name the program opens, outermost layer first; the
tests and PERF.md refer to this list.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

SPANS = (
    # gateway (cluster/gateway.py)
    "ooco.gateway.round",      # one iteration of the runtime thread's loop
    "ooco.gateway.publish",    # token fan-out to the client streams
    "ooco.gateway.lock_wait",  # a submit waiting for the gateway's lock
    # runtime (cluster/runtime.py)
    "ooco.rt.round",           # PoolRuntime.step
    "ooco.rt.checks",          # deadlines, faults, admission state, parked
                               # placements at the top of a round
    "ooco.rt.admit",           # choosing (and admitting) a slot's prefill
    "ooco.rt.plan",            # a slot's batch, chunk, horizon and estimate
    "ooco.rt.dispatch",        # one engine call, with the model's estimate
    "ooco.rt.pull",            # the strict pool's pull-migration decision
    "ooco.rt.migrate",         # one KV transfer between engines
    "ooco.rt.finish",          # a request's final bookkeeping
    # engine (engine/engine.py)
    "ooco.engine.args",        # host arguments of a step program
    "ooco.engine.launch",      # the jitted call
    "ooco.engine.sync",        # the host waiting for the step's tokens
    "ooco.engine.commit",      # token bookkeeping and frees
)


def span(name: str, **stats) -> TraceAnnotation:
    """A host span named ``name`` (one of ``SPANS``) carrying ``stats``."""
    return TraceAnnotation(name, **stats)


#: whether a profiler trace is running (guards stats that cost to compute)
tracing = TraceAnnotation.is_enabled

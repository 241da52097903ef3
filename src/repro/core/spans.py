"""Names the profiler's trace shows: the XLA module of every jitted
stage, and the program's own host spans.

``named_stage(name, fn)`` gives ``fn`` the stage's name before it is
jitted. ``jax.jit`` names its module after the function it wraps, so
``jax.jit(named_stage("cloud_decode_rows", fn))`` lowers to
``module @jit_cloud_decode_rows`` where a lambda would lower to
``jit__lambda``, and the device operations of each stage sit under its
own module in a profiler trace. averylint sees through the wrapper:
``jax.jit(named_stage(name, <fn>))`` marks ``<fn>`` traced exactly as
``jax.jit(<fn>)`` would (``analysis.model.NAME_WRAPPERS``).

``span(name, **args)`` is the one place the program opens a
``jax.profiler.TraceAnnotation``. When a profiler session is active
(``jax.profiler.start_trace``) the span lands in the same trace as the
device's operations, on the same clock, with ``args`` as event stats;
otherwise it costs about a microsecond, so spans are always on. Spans
nest: a layer's self time is its span less its children. ``SPANS`` is
the whole vocabulary (docs/observability.md, "Profiler-clock spans").
"""
from __future__ import annotations

import functools
from typing import Any, Callable

from jax.profiler import TraceAnnotation

SPANS = (
    "engine.submit",          # submit / submit_packet; arg rid
    "engine.pump",
    "inflight.admit",         # one admission, to its first token; rid, hit
    "inflight.step",          # one decode or verify step
    "inflight.step.inputs",   # token, position, write-slot arrays
    "inflight.step.launch",   # the cloud_decode_rows / cloud_verify_rows call
    "inflight.step.fetch",    # greedy pick on the device; ids, counters
                              # (and seg if a row may finish) to the host
    "inflight.step.sample",   # ids into tokens, positions, bookkeeping
    "inflight.finish",        # mask, on_done and resolve; arg rid
    "inflight.draft",         # draft admit and draft steps
)


def span(name: str, **args: Any) -> TraceAnnotation:
    """A profiler span named from ``SPANS``; ``args`` (ints or strings)
    become the event's stats."""
    return TraceAnnotation(name, **args)


def named_stage(name: str, fn: Callable) -> Callable:
    """``fn`` under the stage name its jitted module should carry."""
    @functools.wraps(fn)
    def stage(*args, **kwargs):
        return fn(*args, **kwargs)
    stage.__name__ = stage.__qualname__ = name
    return stage

"""Profiler spans at the layer boundaries of START's decision path.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` while a
profiler session is active, and one shared no-op context otherwise: the
decision path pays about a microsecond a span when nobody traces.  The
spans land on the profiler's host plane, on the same clock as the device
planes, with ``args`` as the event's stats.

Names say the layer: ``sim.*`` the engine's phases of one interval,
``start.*`` the START policy and controller, ``predictor.*`` the fused
interval step.  The engine's and the policy's spans carry the interval
``t``; the controller's ``start.trigger`` and the predictor's spans take
their interval from the ``sim.step`` they nest in, and the predictor's
carry ``n`` (real jobs) and ``nb`` (dispatched rows).  No span sits
inside a per-job or per-task loop.

Counters are plain integers on the object that does the work
(``StragglerPredictor.fused_calls`` and its neighbours), not here.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A profiler span named ``name`` carrying ``args``, or ``OFF``."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **args)
    return OFF

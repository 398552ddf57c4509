"""Host spans and counters in the JAX profiler's trace.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``; a
counter is an empty one named ``repro.count.<name>``, one event per
occurrence. Keyword ids (an experiment's ``seed``, a cache's name) ride
the event as its stats, so all spans of one experiment share an id. The
profiler keeps the events on the same clock as the device's ops; with no
profiler running each costs one enabled-check.

The device side of the same trace is named by ``jax.named_scope``
(``repro.core.engine.PHASES``).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "repro."


def span(name: str, **ids) -> TraceAnnotation:
    """Context manager: the host span ``repro.<name>`` with ``ids``."""
    return TraceAnnotation(PREFIX + name, **ids)


def count(name: str, **ids) -> None:
    """Mark one occurrence of ``repro.count.<name>`` with ``ids``."""
    with TraceAnnotation(PREFIX + "count." + name, **ids):
        pass

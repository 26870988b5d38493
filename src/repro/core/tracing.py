"""Spans and counters of the program's own layers.

A span is a :class:`jax.profiler.TraceAnnotation`.  The profiler records
it only while a trace is active (``jax.profiler.trace`` or
``start_trace``), on the same clock as the device's events, and
``stop_trace`` writes it out with the rest of the trace; with no trace
active, entering one costs under a microsecond.  Spans nest on one
thread, so a child's interval lies inside the span that caused it.
Keyword ids (``span("simjoin.pairs", join=3)``) become the event's
stats.  Span names follow one rule, ``<layer>.<stage>`` in lower case
(``simjoin.sync``), which tells them apart from the runtime's own host
events in a trace.

A counter is a plain integer kept in memory: :func:`count` adds to one,
:func:`counters` copies them all.  A counter takes only values the host
already holds; none waits for, or reads from, the device.
"""
from __future__ import annotations

import jax

_COUNTS: dict[str, int] = {}


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (``<layer>.<stage>``), with ``ids`` as
    its stats."""
    return jax.profiler.TraceAnnotation(name, **ids)


def count(name: str, n: int = 1) -> int:
    """Add ``n`` to the counter ``name``; returns its new value."""
    v = _COUNTS[name] = _COUNTS.get(name, 0) + int(n)
    return v


def counters() -> dict[str, int]:
    """A copy of every counter, by name."""
    return dict(_COUNTS)

"""Spans of the program on the profiler's clock.

``span(name, **args)`` marks a stretch of host work as ``repro.<name>``,
with ``args`` as the event's stats.  A span is recorded exactly when a
profiler session is active (``jax.profiler.start_trace``); it then lands
in the same ``.xplane.pb`` as the device's programs, on the same clock,
nested under whatever span the host thread is in.  Outside a session it
is a shared null context, so no name or argument is formatted.

Spans of one request carry its ``rid``; a count is the number of spans
of a name, and a transfer gives its size in ``bytes``.
"""
from __future__ import annotations

import contextlib

import jax

PREFIX = "repro."
_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """A context manager spanning ``repro.<name>`` while a trace runs."""
    if not jax.profiler.TraceAnnotation.is_enabled():
        return _OFF
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)

"""The program's spans, on the JAX profiler's clock.

Spans record only while a profiler session records (``jax.profiler.trace``
or ``start_trace``, or a capture through the profiler server): they are
``jax.profiler.TraceAnnotation`` host events, so they land in the same
``.xplane.pb`` as the device's operations and share their clock. With no
session, :func:`span` returns one shared no-op context and costs a flag
check. Keyword arguments are counts recorded on the span (event stats in
the trace); counts known only at the end go in through ``set_metadata``.
A count that costs more than a ``len`` is computed only when :func:`on`.

Names are ``echopfl/<layer>`` or ``echopfl/<layer>/<stage>``; every
blocking device-to-host read goes through :func:`fetch`, one ``sync`` span
per read with the ``site`` that asked for it. Never open a span inside a
jitted function: it would record once, at tracing time.
"""
from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation

PREFIX = "echopfl/"


class _Off:
    """The span handed out while nothing records."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_metadata(self, **counts) -> None:
        pass


OFF = _Off()


def on() -> bool:
    """True while a profiler session records the spans."""
    return TraceAnnotation.is_enabled()


def span(name: str, **counts):
    """A host span ``echopfl/<name>`` carrying ``counts``, or :data:`OFF`
    when no profiler session records."""
    if on():
        return TraceAnnotation(PREFIX + name, **counts)
    return OFF


def fetch(x, site: str):
    """``jax.device_get(x)``: the blocking device-to-host read of ``site``."""
    with span("sync", site=site):
        return jax.device_get(x)

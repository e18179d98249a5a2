"""Host spans, written into the JAX profiler's trace.

:func:`span` is a ``jax.profiler.TraceAnnotation``: its events land in the
same ``.xplane.pb`` as the device's ops, on the same clock, so a device idle
gap can be put down to the host span that covers it. The profiler session
is the switch: with no session a span is one inactive TraceMe (about a
microsecond), so spans stay on hot paths. Tags become the event's stats and
the event's name stays the span's name; keep them to ints and short strings
on hot paths. Record a trace with ``jax.profiler.trace(dir,
create_perfetto_trace=True)`` and read it with ``jax.profiler.ProfileData``
or Perfetto.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **tags) -> TraceAnnotation:
    """Context manager for one host span named ``name``, tagged ``tags``."""
    return TraceAnnotation(name, **tags)


def instant(name: str, **tags) -> None:
    """A zero-length span: a mark on the trace's timeline."""
    with TraceAnnotation(name, **tags):
        pass

"""Host spans on the profiler's clock: ``span(name, **counts)``.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro:<name>``. While
a profiler trace is running (``jax.profiler.trace`` / ``start_trace``) it
lands on the host plane of the same ``.xplane.pb`` as the device
operations, on the same clock, so a gap in the device's activity can be
put down to the host phase that was open across it. The keyword counts
become stats of that same event; ``set_metadata`` adds counts known only
once the phase has run. With no trace running a span records nothing and
costs one check of the profiler's enabled flag.

Named scopes (``jax.named_scope``) do the same for device operations: they
land in each operation's ``op_name`` metadata and cost nothing at run time.
Where each span and scope opens is in docs/observability.md.
"""
from __future__ import annotations

import jax

PREFIX = "repro:"


def span(name: str, **counts: int) -> jax.profiler.TraceAnnotation:
    """Context manager for the host span ``repro:<name>``; ``counts`` are
    recorded as the span's stats. ``with span(...) as s`` gives the
    annotation, whose ``set_metadata(**counts)`` adds stats before exit."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **counts)

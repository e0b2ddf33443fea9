"""Host spans in the JAX profiler's trace.

``span(name)`` marks a stretch of host time as ``name``; ``span(name,
step=n)`` marks step ``n`` of a loop of steps. A span records only while a
profiler runs (``jax.profiler.start_trace``) and does nothing otherwise:
whether a profiler runs is the switch. Device work is named inside the
compiled program with ``jax.named_scope`` (DESIGN.md §13).
"""

from __future__ import annotations

import jax


def span(name: str, *, step: int | None = None):
    """A context manager: a ``TraceAnnotation``, or with ``step`` a
    ``StepTraceAnnotation``."""
    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=step)

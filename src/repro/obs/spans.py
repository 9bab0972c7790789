"""Wall-clock spans on the profiler's clock.

`span(name, **args)` marks a stretch of host work on the served path
(`ann.*` names). It opens `jax.profiler.TraceAnnotation(name, **args)`:
a running profiler records the span on its host plane, on the same clock
as the device ops of the same `.xplane.pb`; with no profiler running the
annotation records nothing and costs about a microsecond.

Parent and child follow from nesting on one thread. Unlike the
virtual-time `Tracer`, these spans carry no times of their own: their
time is the profiler's.
"""
from __future__ import annotations

from typing import Any, ContextManager

__all__ = ["span"]


def span(name: str, **args: Any) -> ContextManager[Any]:
    """A context that marks `name` (with `args`) on the profiler's host
    plane while a profiler trace runs."""
    import jax.profiler
    return jax.profiler.TraceAnnotation(name, **args)

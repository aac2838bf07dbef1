"""Host spans in the profiler's own trace.

`span(name, **counts)` is a `jax.profiler.TraceAnnotation`: under a
profiler session (`jax.profiler.start_trace`) it records one host event
named `name` with `counts` as its arguments, on the clock the device
events of the same trace are stamped with; outside a session it costs
one activity check.  A `step_num` count makes it a
`StepTraceAnnotation`, which the profiler takes as a step boundary.

Names are dotted, `<component>.<phase>`, and a span whose name ends in
`.wait` holds the only places where the host blocks on the device, so
a reader can split a step's host time from its wait on the chip
(docs/observability.md lists the spans).  A span never syncs by itself.
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation


def span(name: str, **counts):
    """A host span for the profiler's trace; `step_num` marks a step."""
    if "step_num" in counts:
        return StepTraceAnnotation(name, **counts)
    return TraceAnnotation(name, **counts)

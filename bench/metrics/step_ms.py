"""step_ms: mean device time of one execution of the serving executable
(``search_step``), over the executions wholly inside the traced span."""

from bench.metrics_lib import STEP_MODULE
from bench import trace


def read(ctx):
    span = trace.window_ns(ctx.events or [])
    if span is None:
        return None
    calls = trace.modules(ctx.events, STEP_MODULE, *span)
    if not calls:
        return None
    return 1e-6 * sum(e.dur_ns for e in calls) / len(calls)

"""idle_share: 1 - the union of the device's op intervals over the traced
span, as a percentage of the span (mean over the chips used)."""

from bench import trace


def read(ctx):
    span = trace.window_ns(ctx.events or [])
    ops = trace.device_ops(ctx.events or [])
    if span is None or not ops:
        return None
    lo, hi = span
    busy = sum(trace.busy_ns(v, lo, hi) for v in ops.values()) / len(ops)
    return 100.0 * (1.0 - busy / (hi - lo))

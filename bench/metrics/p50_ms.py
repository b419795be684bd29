"""p50_ms: median latency over every query due in the window, timed from
its scheduled arrival; a shed or unanswered query counts as +inf."""

from bench.metrics_lib import percentile_ms


def read(ctx):
    return percentile_ms(ctx.latency_s, ctx.answered, 50)

"""dispatch_ms: mean length of the program's ``serve.flush`` spans (take,
pad, enqueue one flush) wholly inside the traced span, on the profiler's
clock."""

from bench import spans


def read(ctx):
    return spans.span_ms(ctx.events or [], "serve.flush")

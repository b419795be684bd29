"""flush_fill: real query rows over padded bucket rows, across every flush
of the window (``TopologyReport.flush_sizes`` and the bucket ladder)."""

from bench.metrics_lib import flush_fill


def read(ctx):
    return flush_fill(ctx.flush_sizes, ctx.buckets)

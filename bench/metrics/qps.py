"""qps: queries answered with a well-formed row, over the host-clock time
of the whole window's ``run()`` call."""


def read(ctx):
    return int(ctx.good.sum()) / ctx.window_s

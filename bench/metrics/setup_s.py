"""setup_s: process start to the window's start (host clock): corpus,
index build, compiles, warm-up."""


def read(ctx):
    return ctx.setup_s

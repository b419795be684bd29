"""recall_at_10: recall@10 of every query answered in the window against
the brute-force reference."""


def read(ctx):
    return ctx.recall

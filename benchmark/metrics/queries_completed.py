"""Queries completed in the measured window: the samples a tail stands on."""


def read(ctx):
    return len(ctx.window_queries)

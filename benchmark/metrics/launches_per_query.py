"""Program launches per query in the traced slice: the program's exact
count (``plan/execs/base.py launch_stats``) over the queries completed."""


def read(ctx):
    if not ctx.slice_queries:
        return None
    return ctx.slice_launches / len(ctx.slice_queries)

"""What ``execute()`` does after the last batch, per query in the traced
slice, in milliseconds: the sum of the program's ``query.finish`` spans (the
per-exec metric report, which fetches every lazily kept row count from the
device with a transfer of its own, and plan cleanup) over the queries
completed.  The device is idle throughout."""
from benchmark.span_sums import ms_per_query

SPANS = ("query.finish",)


def read(ctx):
    return ms_per_query(ctx, "query.finish")

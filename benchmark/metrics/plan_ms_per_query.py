"""Planning time per query in the traced slice, in milliseconds: the sum of
the program's ``query.plan`` spans (logical plan -> physical plan, on the
client's thread before anything is launched) over the queries completed."""
from benchmark.span_sums import ms_per_query

SPANS = ("query.plan",)


def read(ctx):
    return ms_per_query(ctx, "query.plan")

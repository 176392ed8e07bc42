"""The ORDER BY's own host-side work per query in the traced slice, in
milliseconds: the sum of the program's ``sort.range`` spans (after the child
is drained: coalesce or bound sampling with its host sync and routing, then
each partition's local sort dispatch) over the queries completed."""
from benchmark.span_sums import ms_per_query

SPANS = ("sort.range",)


def read(ctx):
    return ms_per_query(ctx, "sort.range")

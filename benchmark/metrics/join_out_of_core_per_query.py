"""Join partitions per query in the traced slice that took the
sub-partitioned out-of-core path: the program's ``join.out_of_core`` spans
(one a reduce partition whose two sides pass the in-core bound) over the
queries completed.  0 where every reduce group was joined in core; None
where the program has no such span (it names its spans in
``tracing.static_ranges()``)."""
from benchmark.span_sums import intervals

SPAN = "join.out_of_core"
SPANS = (SPAN,)


def read(ctx):
    from spark_rapids_tpu.utils import tracing
    if not ctx.slice_queries or SPAN not in tracing.static_ranges():
        return None
    return len(intervals(ctx, SPAN)) / len(ctx.slice_queries)

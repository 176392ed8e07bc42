"""Launches of a join's expansion or condition program per query in the
traced slice that were run again at a larger capacity: the program's
``join.retry`` spans (one a launch whose pair region, output rows or
gather bytes fell short, from its dispatch to the status that condemned it)
over the queries completed.  0 where every first guess held; None where the
program has no such span (it names its spans in
``tracing.static_ranges()``).  The span is written when the launch is
condemned and is not in the profiler's trace, so it names no idle gap."""
from benchmark.span_sums import intervals

SPAN = "join.retry"


def read(ctx):
    from spark_rapids_tpu.utils import tracing
    if not ctx.slice_queries or SPAN not in tracing.static_ranges():
        return None
    return len(intervals(ctx, SPAN)) / len(ctx.slice_queries)

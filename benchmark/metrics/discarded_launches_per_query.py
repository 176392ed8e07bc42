"""Launches of fused programs per query in the traced slice whose output
was thrown away and run again larger: the program's ``fused.discard`` spans
(one a launch ``_converge`` condemned, whatever was too small) over the
queries completed.  0 in a process whose capacities have converged; None
where the program has no such span (it names its spans in
``tracing.static_ranges()``).  The span is written when the launch is
condemned and is not in the profiler's trace, so it names no idle gap."""
from benchmark.span_sums import intervals

SPAN = "fused.discard"


def read(ctx):
    from spark_rapids_tpu.utils import tracing
    if not ctx.slice_queries or SPAN not in tracing.static_ranges():
        return None
    return len(intervals(ctx, SPAN)) / len(ctx.slice_queries)

"""Admission per executed query in the traced slice, in milliseconds: the sum
of the program's ``serving.admission`` spans (``tracing.span_log``; one a
submission that reached admission: the wait for a slot and for the byte
budget, priority-then-FIFO) over the queries the slice ran.  A cache hit or a
follower's answer takes no admission and is not counted.  None where no
``serving.admission`` span was written: a program whose serving spans go only
to its query traces, or a closed loop.  No ``SPANS``: admission waits run
beside other queries' work, so they name no idle gap."""
from benchmark.span_sums import intervals

SPAN = "serving.admission"


def read(ctx):
    ivs = intervals(ctx, SPAN)
    if not ivs or not ctx.slice_queries:
        return None
    return 1e3 * sum(e - s for s, e in ivs) / len(ctx.slice_queries)

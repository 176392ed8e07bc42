"""Share of the traced slice in which a thread that asked for the
interpreter lock did not have it, in percent: union of the program's
``host.lock_wait`` spans (``tracing.span_log``), one a tick of its sampler
thread that ran more than a millisecond after it was due, from due to ran.
The sampler asks every 5 ms, so the share is what any other thread of the
process that wanted the lock would have waited.  0.0 where the program names
the span (``tracing.static_ranges()``) and none was written; None where it
does not.  No ``SPANS``: the span is written after the fact and is not in the
profiler's trace, so it names no idle gap."""
from benchmark.trace_digest import span_share_pct

SPAN = "host.lock_wait"


def read(ctx):
    from spark_rapids_tpu.utils import tracing
    lo, hi = ctx.slice_interval
    if hi <= lo or SPAN not in tracing.static_ranges():
        return None
    return span_share_pct(ctx.spans, SPAN, lo, hi) or 0.0

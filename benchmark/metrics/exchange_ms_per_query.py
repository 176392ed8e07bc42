"""The exchange's own work per query in the traced slice, in milliseconds:
the sum of the program's ``exchange.write`` spans (per map batch: slice
dispatch, counts sync or download, transport write) and ``exchange.read``
spans (each pull from the transport's reader, the coalescing concat) over
the queries completed.  The child's compute is in neither."""
from benchmark.span_sums import ms_per_query

SPANS = ("exchange.write", "exchange.read")


def read(ctx):
    return ms_per_query(ctx, *SPANS)

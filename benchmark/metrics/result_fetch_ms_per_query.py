"""The final device -> host transfer and row building per query in the
traced slice, in milliseconds: the sum of the program's ``query.fetch`` spans
(in ``collect()``, after ``execute()`` returned) over the queries completed.
The transfer waits for whatever the device still has queued, so the span
holds the tail of the last launches too."""
from benchmark.span_sums import ms_per_query

SPANS = ("query.fetch",)


def read(ctx):
    return ms_per_query(ctx, "query.fetch")

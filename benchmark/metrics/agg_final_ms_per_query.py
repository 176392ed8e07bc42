"""The final aggregate's and the HAVING's own host-side work per query in
the traced slice, in milliseconds: the sum of the program's ``agg.final``
spans (one a reduce group: pull the group's pieces from the exchange's read
side, dispatch the combine; out of core, one a bucket's merge and finalize)
and ``batch.shrink`` spans (``maybe_shrink`` of the filter's output: the
host sync on its row count, which waits for the combine, and the regather)
over the queries completed.  ``SPANS`` names both, so the device's idle
gaps under them get their names."""
from benchmark.span_sums import ms_per_query

SPANS = ("agg.final", "batch.shrink")


def read(ctx):
    return ms_per_query(ctx, *SPANS)

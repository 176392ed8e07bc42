"""Host decode per query in the traced slice, in thread-seconds: the sum of
the program's ``scan.decode`` spans (one per decoded chunk, on the reader
pool; a file's ``scan.open`` lies inside its first one, the producer's wait
on a full prefetch queue in none) over the queries completed.  A sum over
threads, so it can pass the query's own time.  No ``SPANS``: see
``scan_open_ms_per_query``."""
from benchmark.span_sums import seconds_per_query


def read(ctx):
    return seconds_per_query(ctx, "scan.decode")

"""Time per query, in milliseconds, in which at least one reader-pool thread
was opening a file: the union of the program's ``scan.open`` spans (footer
parse, row-group pruning, coalesced open) over the queries of the traced
slice.  A union, because the files of several scan partitions open at once.
No ``SPANS``: the span runs beside ``scan.wait`` on another thread and would
take over the idle gaps that ``scan.wait`` names."""
from benchmark.span_sums import intervals
from benchmark.trace_digest import union_seconds


def read(ctx):
    ivs = intervals(ctx, "scan.open")
    if not ivs or not ctx.slice_queries:
        return None
    return 1e3 * union_seconds(ivs) / len(ctx.slice_queries)

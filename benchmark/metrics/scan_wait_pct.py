"""Share of the traced slice in which a task waited for a decoded chunk:
union of the program's ``scan.wait`` spans (``tracing.span_log``).  Two scan
partitions may wait at once, so a sum could pass 100; the union cannot."""
from benchmark.trace_digest import span_share_pct

SPANS = ("scan.wait",)


def read(ctx):
    return span_share_pct(ctx.spans, "scan.wait", *ctx.slice_interval)

"""Share of the traced slice spent in the Arrow -> HBM upload: union of the
program's ``scan.upload`` spans (``tracing.span_log``)."""
from benchmark.trace_digest import span_share_pct

SPANS = ("scan.upload",)


def read(ctx):
    return span_share_pct(ctx.spans, "scan.upload", *ctx.slice_interval)

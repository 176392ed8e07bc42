"""Sums over the program's spans (``tracing.span_log``: name, start, end on
one clock) for the per-layer readers that report a layer's own time per
query.  A sum counts every thread's seconds; where threads overlap and wall
time is meant, a reader takes ``trace_digest.union_seconds`` instead."""
from benchmark.trace_digest import clip


def intervals(ctx, *names) -> list:
    """The (start, end) pairs of the spans called one of ``names``, clipped
    to the traced slice."""
    return clip([(s, e) for n, s, e in ctx.spans if n in names],
                *ctx.slice_interval)


def seconds_per_query(ctx, *names):
    """Summed seconds of the spans called one of ``names`` over the queries
    the slice completed; None where no such span was recorded (a program
    without the span)."""
    ivs = intervals(ctx, *names)
    if not ivs or not ctx.slice_queries:
        return None
    return sum(e - s for s, e in ivs) / len(ctx.slice_queries)


def ms_per_query(ctx, *names):
    """``seconds_per_query`` in milliseconds."""
    s = seconds_per_query(ctx, *names)
    return None if s is None else 1e3 * s

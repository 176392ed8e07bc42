"""What the host does for one call of a fused program, in milliseconds: the
program's ``fused.batch`` spans (key building, program lookup, dispatch,
retry loop, feedback) less the ``fused.feedback`` spans inside them (the task
thread blocked on the device for the capacity feedback), over the number of
``fused.batch`` spans in the traced slice."""
from benchmark.span_sums import intervals

SPANS = ("fused.batch",)


def read(ctx):
    batches = intervals(ctx, "fused.batch")
    if not batches:
        return None
    blocked = intervals(ctx, "fused.feedback")
    return 1e3 * (sum(e - s for s, e in batches)
                  - sum(e - s for s, e in blocked)) / len(batches)

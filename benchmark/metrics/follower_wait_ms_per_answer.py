"""A single-flight follower's wait per submission in the traced slice, in
milliseconds: the sum of the program's ``serving.follow`` spans (one a
submission that found the same plan running and waited for that leader's
answer) over the slice's submissions (``serving.submit`` spans: every
arrival, answered from the cache, after a wait, or by running).  0.0 where
the program names the span (``tracing.static_ranges()``) and no follower
waited; None where it does not, or writes no ``serving.submit`` span to the
log.  No ``SPANS``: a follower waits beside its leader's work."""
from benchmark.span_sums import intervals

SPAN = "serving.follow"


def read(ctx):
    from spark_rapids_tpu.utils import tracing
    if SPAN not in tracing.static_ranges():
        return None
    submissions = intervals(ctx, "serving.submit")
    if not submissions:
        return None
    return 1e3 * sum(e - s for s, e in intervals(ctx, SPAN)) / len(
        submissions)

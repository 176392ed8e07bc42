"""What handing one scan batch's planes to the runtime costs the task's
thread, in milliseconds: the program's ``upload.put`` spans (one a host plane:
a column's data, validity or offsets through ``jnp.asarray``), each clipped to
the ``scan.upload`` spans of the traced slice, summed, over the number of
``scan.upload`` spans.  A plane put outside a scan's upload is not counted.
With ``scan_stage_ms_per_batch`` it partitions ``scan.upload``.  None where
the program has no such span (it names its spans in
``tracing.static_ranges()``) or the slice holds no upload.  No ``SPANS``: the
span is nested in ``scan.upload``, which names the idle gaps."""
from benchmark.span_sums import intervals
from benchmark.trace_digest import clip, merge

PUT, UPLOAD = "upload.put", "scan.upload"


def put_and_upload(ctx):
    """(seconds of ``upload.put`` inside ``scan.upload``, seconds of
    ``scan.upload``, number of ``scan.upload`` spans) of the slice."""
    from spark_rapids_tpu.utils import tracing
    uploads = intervals(ctx, UPLOAD)
    if not uploads or PUT not in tracing.static_ranges():
        return None
    puts = intervals(ctx, PUT)
    inside = sum(e - s for lo, hi in merge(uploads)
                 for s, e in clip(puts, lo, hi))
    return inside, sum(e - s for s, e in uploads), len(uploads)


def read(ctx):
    got = put_and_upload(ctx)
    if got is None:
        return None
    put_s, _upload_s, batches = got
    return 1e3 * put_s / batches

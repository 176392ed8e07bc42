"""The host's own passes over one scan batch, Arrow -> padded numpy planes,
in milliseconds: the program's ``scan.upload`` spans less the ``upload.put``
spans inside them (``scan_put_ms_per_batch``: the hand-over to the runtime),
over the number of ``scan.upload`` spans in the traced slice.  What is left
is ``arrow_to_batch``'s conversions, casts, null passes and padded copies.
None where the program has no ``upload.put`` span or the slice holds no
upload.  No ``SPANS``: ``scan_upload_pct`` names the idle gaps."""
from benchmark.metrics.scan_put_ms_per_batch import put_and_upload


def read(ctx):
    got = put_and_upload(ctx)
    if got is None:
        return None
    put_s, upload_s, batches = got
    return 1e3 * (upload_s - put_s) / batches

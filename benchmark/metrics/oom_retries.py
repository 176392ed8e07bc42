"""Device out-of-memory events plus task retries and split retries in the
traced slice, as ``chip_smoke.py`` reads them (``memory/arena.py``'s global
count; the per-query trace's ``task_retry_count``/``task_split_retry_count``)."""


def read(ctx):
    return ctx.slice_oom + ctx.slice_retries

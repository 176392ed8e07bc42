"""Least time over device busy time in the traced slice, bound by HBM
bandwidth: the bytes of the columns each completed query has to read
(``cost.least_bytes``) over the chip's peak bandwidth (``peaks.json``), over
the seconds in which an operation ran on the device."""
from benchmark import cost


def read(ctx):
    if ctx.trace is None or not ctx.slice_queries:
        return None
    least = sum(cost.least_bytes(ctx.queries[q.name], ctx.tables[q.table],
                                 q.rows) for q in ctx.slice_queries)
    least_s = least / (cost.peak(ctx.device_kind, "hbm_gb_per_s") * 1e9)
    return 100.0 * least_s / ctx.trace["busy_s"]

"""Share of the traced slice (the ``bench.window`` annotation) in which no
operation ran on the device, from the profiler's ``xplane.pb``."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])

"""XLA backend compiles inside the measured window (``jax.monitoring``).
Zero unless the warm-up missed a shape."""


def read(ctx):
    return ctx.compiles_in_window

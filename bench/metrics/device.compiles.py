"""Programs built (compiled, or loaded from the compile cache) inside the
measured window.  Set-up warms every shape, so it should read 0."""


def read(ctx):
    return float(ctx.compiles_in_window)

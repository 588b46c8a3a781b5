"""Device time per window microbatch of the frontier pass and its pair
count (``_frontier_count`` in ``core/queries_jax.py``), in ms, from the
trace."""


def read(ctx):
    return ctx.stage_ms(("_frontier_count",), "window")

"""Requests per dispatched microbatch over the window, as a share of
``batch_max`` (the ``Frontend``'s own counters)."""
from bench.harness import BATCH_MAX


def read(ctx):
    st = ctx.frontend
    if not st.batches:
        return None
    return 100.0 * st.completed / st.batches / BATCH_MAX

"""Mean time per dispatched batch that the ``Frontend``'s dispatcher held a
queued request open for its batch window, in ms: ``FrontendStats.hold_s /
batches`` (the dispatcher's wait with a request queued and no batch due;
the ``frontend.hold`` span)."""


def read(ctx):
    st = ctx.frontend
    hold = getattr(st, "hold_s", None)
    if hold is None or not st.batches:
        return None
    return 1e3 * hold / st.batches

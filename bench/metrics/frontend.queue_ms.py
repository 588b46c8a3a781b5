"""Mean time a request served in the window waited inside the ``Frontend``,
from its submit until its batch was handed to the server, in ms: the hold
of the batch window, the wait behind the batch already on the device, and
the dispatcher's wake-up.  ``FrontendStats.queue_wait_s / dispatched``, the
``Frontend``'s own counters."""


def read(ctx):
    st = ctx.frontend
    n = getattr(st, "dispatched", 0)
    if not n:
        return None
    return 1e3 * st.queue_wait_s / n

"""Mean host wall time of one ``DeviceQueryServer.window`` call made by the
``Frontend`` in the window, in ms: device work, host syncs, transfers and
the split of the answers, as the dispatcher thread waits for them."""


def read(ctx):
    t = [end - start for kind, _, start, end in ctx.engine_calls
         if kind == "window"]
    return 1e3 * sum(t) / len(t) if t else None

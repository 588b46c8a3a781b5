"""Mean host wall time of one ``DeviceQueryServer.knn`` call made by the
``Frontend`` in the window, in ms: the first round and every escalation
round on the device, the host's one sync per round, and the transfer of
the answers, as the dispatcher thread waits for them."""


def read(ctx):
    t = [end - start for kind, _, start, end in ctx.engine_calls
         if kind == "knn"]
    return 1e3 * sum(t) / len(t) if t else None

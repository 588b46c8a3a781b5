"""The 99th percentile latency of all requests due in the window, in ms,
as ``p99_ms`` would read it (host clock; a failed request counts as
waiting ``seconds + 60`` s).  Kept here, without a bound, because stalls
of whole seconds in some runs and not others make it swing more than any
bound could hold (PERF.md, Findings, PR 12)."""
from bench.harness import end_to_end


def read(ctx):
    values, _, _ = end_to_end(ctx.records, ctx.window, ctx.seconds, 0.0,
                              ctx.build)
    return values["p99_ms"]

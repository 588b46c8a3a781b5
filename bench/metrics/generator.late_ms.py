"""99th percentile of how late the open-loop generator sent a request
against the instant it was due, in ms (host clock).  A starved generator
shows here, not as a fast server."""
import numpy as np


def read(ctx):
    if ctx.cell.traffic["loop"] != "open" or not ctx.records.due:
        return None
    late = np.sort(np.asarray(ctx.records.sent) - np.asarray(ctx.records.due))
    return float(late[int(np.ceil(0.99 * len(late))) - 1] * 1e3)

"""The program's own spans of the measured window, for the per-layer
metrics that read them.

A ``--trace 1`` run turns the program's spans on (``repro.tracing``
records while the profiler traces).  A program without that module, or a
window that holds no span of the name asked for, gives ``None``: the metric
is then left out of the result line.
"""
from __future__ import annotations

import bisect
import collections


def in_window(ctx, name: str):
    """The records of ``name`` that started inside the window, or None."""
    try:
        from repro import tracing
    except ImportError:
        return None
    recs = tracing.recorded(*ctx.window, name=name)
    return recs or None


def nested(outer, inner) -> list:
    """For each record of ``outer``, the records of ``inner`` that ran
    inside it, on its thread."""
    by_thread = collections.defaultdict(list)
    for r in sorted(inner, key=lambda r: r.t_start):
        by_thread[r.thread].append(r)
    starts = {t: [r.t_start for r in rs] for t, rs in by_thread.items()}
    out = []
    for o in outer:
        rs = by_thread.get(o.thread, [])
        i = bisect.bisect_left(starts.get(o.thread, []), o.t_start)
        inside = []
        while i < len(rs) and rs[i].t_start <= o.t_end:
            if rs[i].t_end <= o.t_end:
                inside.append(rs[i])
            i += 1
        out.append(inside)
    return out


def field_share(ctx, name: str, part: str, whole: str):
    """100 x the sum of field ``part`` over the sum of field ``whole``, over
    the window's ``name`` spans; None where the whole sums to 0."""
    recs = in_window(ctx, name)
    if recs is None:
        return None
    den = sum(r.fields.get(whole, 0) for r in recs)
    if not den:
        return None
    return 100.0 * sum(r.fields.get(part, 0) for r in recs) / den

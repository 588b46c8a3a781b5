"""Share of the pair scan's slots that held a real (window, leaf) pair, in
percent: the intersecting pairs over the power-of-two pair buckets that
``pair_window_ids`` scanned, summed over the window's ``query.window``
spans (fields ``pairs`` and ``pair_slots``)."""
from bench import spans


def read(ctx):
    return spans.field_share(ctx, "query.window", "pairs", "pair_slots")

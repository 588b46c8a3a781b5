"""Share of the device id pack's slots that held a qualifying id, in
percent: the ids returned over the power-of-two id buckets that
``_fused_id_pack`` filled, summed over the window's ``query.window`` spans
(fields ``ids`` and ``id_slots``)."""
from bench import spans


def read(ctx):
    return spans.field_share(ctx, "query.window", "ids", "id_slots")

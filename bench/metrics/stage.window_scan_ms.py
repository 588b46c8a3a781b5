"""Device time per window microbatch of the pair scan and the id pack
(``_fused_pack_scan``, ``_fused_id_pack`` in ``core/queries_jax.py``),
in ms, from the trace."""


def read(ctx):
    return ctx.stage_ms(("_fused_pack_scan", "_fused_id_pack"), "window")

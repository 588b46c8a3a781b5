"""Device time per k-NN microbatch of the jitted k-NN stages, in ms, from
the trace: every round's candidate selection, ``pair_dist2`` scan and
top-k merge (``_knn_core_fused``), the packing of the queries whose
certificate failed (``_knn_pending``) and the scatter of an escalation
round's answers (``_knn_merge_round``), in ``core/queries_jax.py``."""


def read(ctx):
    return ctx.stage_ms(("_knn_core_fused", "_knn_pending",
                         "_knn_merge_round"), "knn")

"""Host time per window microbatch inside the query engine between its
blocking reads of the device, in ms: each ``query.window`` span of the
window less the ``query.sync`` spans nested in it (``_window_batch_fused``
in ``core/queries_jax.py``), averaged over the microbatches."""
from bench import spans


def read(ctx):
    windows = spans.in_window(ctx, "query.window")
    if windows is None:
        return None
    syncs = spans.in_window(ctx, "query.sync") or []
    host = [(w.t_end - w.t_start) - sum(s.t_end - s.t_start for s in inner)
            for w, inner in zip(windows, spans.nested(windows, syncs))]
    return 1e3 * sum(host) / len(host)

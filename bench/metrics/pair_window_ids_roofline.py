"""Share of the HBM roofline that the ``pair_window_ids`` kernel reached in
the window, in percent.  Bytes: the (window, leaf) pairs whose boxes
intersect, counted by the benchmark from the leaf boxes and the window's
requests, times ``work.bytes_pair_window_ids`` per pair.  Time: the
kernel's device time in the trace.  Peak: ``peaks.CHIP_PEAKS``."""
from bench import peaks, work


def read(ctx):
    t = ctx.kernel_s("pair_window_ids")
    reqs = ctx.requests(0)
    if not t or reqs is None:
        return None
    lo, hi, _ = reqs
    pairs = work.window_pairs(ctx.leaf_lo, ctx.leaf_hi, lo, hi)
    b = work.bytes_pair_window_ids(pairs, ctx.leaf_size, lo.shape[1])
    return 100.0 * b / t / peaks.chip_peaks(ctx.device_kind)["hbm_bw"]

"""Share of the HBM roofline that the ``pair_dist2`` kernel reached in the
window, in percent.  Bytes: for each k-NN request sent in the window, the
leaves whose box lies within the reference's k-th distance
(``work.knn_leaves_each``: an exact k-NN has to read them, whatever budget
the program scans by), times ``work.bytes_pair_dist2`` per pair.  Those
leaves are counted on an evenly spaced sample of ``SAMPLE`` of the
window's requests (all, where fewer were sent) and scaled to all of them.
Time: the kernel's device time in the trace.  Peak: ``peaks.CHIP_PEAKS``."""
import numpy as np

from bench import peaks, work

SAMPLE = 2048


def read(ctx):
    t = ctx.kernel_s("pair_dist2")
    reqs = ctx.requests(1)
    if not t or reqs is None or ctx.reference is None:
        return None
    qs, _, k = reqs
    pick = np.unique(np.linspace(0, len(qs) - 1, min(len(qs), SAMPLE))
                     .round().astype(np.int64))
    kth = np.array([ctx.reference.knn_dist2(qs[i], int(k[i]))[-1]
                    for i in pick])
    leaves = work.knn_leaves_each(ctx.leaf_lo, ctx.leaf_hi, qs[pick], kth)
    pairs = leaves.mean() * len(qs)
    b = work.bytes_pair_dist2(pairs, ctx.leaf_size, qs.shape[1])
    return 100.0 * b / t / peaks.chip_peaks(ctx.device_kind)["hbm_bw"]

"""What the served kernels must move at the least, counted by the
benchmark from the index's leaf boxes and the requests it sent.

The byte models are copied from the program's ``repro/roofline.py``.  The
pair counts are the benchmark's own: they depend on the requests and the
leaf layout, never on how the program batched or padded them, so a
roofline share built on them cannot pass 100% by counting padding.
"""
from __future__ import annotations

import numpy as np


def bytes_pair_window_ids(p: int, s: int, d: int) -> int:
    """Fused (query, leaf) pair window scan: per pair one leaf block of
    points + ids + count + one query box in, one id row + count out."""
    per_pair = s * d * 4 + s * 4 + 4 + 2 * d * 4 + s * 4 + 4
    return p * per_pair


def bytes_pair_dist2(p: int, s: int, d: int) -> int:
    """Fused (query, leaf) candidate-distance scan: per pair one leaf
    block of points + its count + one query point in, one row of squared
    distances out."""
    per_pair = s * d * 4 + 4 + d * 4 + s * 4
    return p * per_pair


def window_pairs_each(leaf_lo: np.ndarray, leaf_hi: np.ndarray,
                      los: np.ndarray, his: np.ndarray,
                      block: int = 64) -> np.ndarray:
    """Each window's (window, leaf) pairs: the leaves whose boxes it meets,
    which an exact window scan has to read."""
    out = [np.zeros(0, dtype=np.int64)]
    for a in range(0, len(los), block):
        lo, hi = los[a:a + block, None, :], his[a:a + block, None, :]
        hit = np.all((leaf_lo[None] <= hi) & (leaf_hi[None] >= lo), axis=2)
        out.append(hit.sum(axis=1))
    return np.concatenate(out)


def window_pairs(leaf_lo: np.ndarray, leaf_hi: np.ndarray, los: np.ndarray,
                 his: np.ndarray, block: int = 64) -> int:
    """All the windows' (window, leaf) pairs."""
    return int(window_pairs_each(leaf_lo, leaf_hi, los, his, block).sum())


def knn_leaves_each(leaf_lo: np.ndarray, leaf_hi: np.ndarray,
                    qs: np.ndarray, kth_d2: np.ndarray,
                    block: int = 64) -> np.ndarray:
    """Each query's leaves whose box lies within its k-th nearest squared
    distance ``kth_d2`` (squared box mindist at most that): the leaves an
    exact k-NN has to read, whatever budget it scans them by."""
    lo = np.asarray(leaf_lo, dtype=np.float64)[None]
    hi = np.asarray(leaf_hi, dtype=np.float64)[None]
    out = [np.zeros(0, dtype=np.int64)]
    for a in range(0, len(qs), block):
        q = np.asarray(qs[a:a + block], dtype=np.float64)[:, None, :]
        g = np.maximum(lo - q, 0.0) + np.maximum(q - hi, 0.0)
        mind = np.sum(g * g, axis=2)
        out.append((mind <= kth_d2[a:a + block, None]).sum(axis=1))
    return np.concatenate(out)

"""Pure-jnp oracles for every Pallas kernel (the correctness contracts)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def partition_assign_ref(points, split_dim, split_val, *, levels: int):
    """Reference tree routing: plain gathers, no tiling."""
    n = points.shape[0]
    g = jnp.zeros(n, dtype=jnp.int32)
    rows = jnp.arange(n)
    for level in range(levels):
        dim = split_dim[level, g]
        val = split_val[level, g]
        coord = points[rows, dim]
        g = g * 2 + (coord > val).astype(jnp.int32)
    return g


def pairwise_dist2_ref(queries, points, valid):
    """Reference masked squared distances: direct subtraction."""
    d2 = jnp.sum(
        (queries[:, None, :] - points[None, :, :]) ** 2, axis=-1
    ).astype(jnp.float32)
    big = jnp.finfo(jnp.float32).max
    return jnp.where(valid[None, :] > 0, d2, big)


def knn_topk_ref(queries, points, valid, k: int):
    """Reference k-NN: full distance matrix + top_k."""
    d2 = pairwise_dist2_ref(queries, points, valid)
    neg, idx = jax.lax.top_k(-d2, k)
    return idx, -neg


def window_count_ref(lo, hi, points, valid):
    """Reference window counting: one broadcast containment test."""
    inside = jnp.all(
        (points[None, :, :] >= lo[:, None, :])
        & (points[None, :, :] <= hi[:, None, :]),
        axis=-1,
    ) & (valid[None, :] > 0)
    return jnp.sum(inside, axis=1).astype(jnp.int32)


def window_count_gathered_ref(lo, hi, points, valid):
    """Reference for the per-query gathered layout: (nq, npp, d) points."""
    inside = jnp.all(
        (points >= lo[:, None, :]) & (points <= hi[:, None, :]), axis=-1
    ) & (valid > 0)
    return jnp.sum(inside, axis=1).astype(jnp.int32)


def window_mask_gathered_ref(lo, hi, points, valid):
    """Reference containment mask for the per-query gathered layout."""
    inside = jnp.all(
        (points >= lo[:, None, :]) & (points <= hi[:, None, :]), axis=-1
    ) & (valid > 0)
    return inside.astype(jnp.int32)


def gathered_dist2_ref(queries, points, valid):
    """Reference per-query gathered squared distances: (nq, npp, d) points."""
    d2 = jnp.sum((points - queries[:, None, :]) ** 2, axis=-1).astype(
        jnp.float32
    )
    big = jnp.finfo(jnp.float32).max
    return jnp.where(valid > 0, d2, big)


def box_hits_tiled_ref(lo, hi, qlo, qhi):
    """Reference box-intersection mask: (n, nq), f32 compare after widening
    any bf16 storage (matching the kernel's in-register cast)."""
    lo = lo.astype(jnp.float32)
    hi = hi.astype(jnp.float32)
    inter = (lo[:, None, :] <= qhi[None, :, :]) & (
        hi[:, None, :] >= qlo[None, :, :]
    )
    return jnp.all(inter, axis=-1).astype(jnp.int32)


def pair_window_ids_ref(qlo, qhi, leaf_lo, leaf_hi, leaf_pts, leaf_ids,
                        leaf_counts, q_idx, leaf_idx, pair_valid):
    """Reference fused pair scan: plain gathers out of the (d, L, S)
    leaf table, ids-or-minus-one."""
    lo_p = qlo[q_idx]                         # (P, d)
    hi_p = qhi[q_idx]
    pts = jnp.moveaxis(leaf_pts[:, leaf_idx], 0, -1)  # (P, S, d)
    ids = leaf_ids[leaf_idx]                  # (P, S)
    s = leaf_pts.shape[2]
    valid = (
        jnp.arange(s, dtype=jnp.int32)[None, :]
        < leaf_counts[leaf_idx][:, None]
    ) & (pair_valid[:, None] > 0)
    box_ok = jnp.all(
        (leaf_lo[leaf_idx].astype(jnp.float32) <= hi_p)
        & (leaf_hi[leaf_idx].astype(jnp.float32) >= lo_p),
        axis=1,
    )
    inside = jnp.all(
        (pts >= lo_p[:, None, :]) & (pts <= hi_p[:, None, :]), axis=2
    ) & valid & box_ok[:, None]
    counts = jnp.sum(inside.astype(jnp.int32), axis=1)
    return jnp.where(inside, ids, -1), counts


def leaf_mindist_ref(queries, leaf_lo, leaf_hi):
    """Reference squared box mindists: (nq, L).

    Accumulates per dimension in the kernel's order so results are
    bit-identical (a fused jnp.sum can round differently by one ulp)."""
    lo = leaf_lo.astype(jnp.float32)
    hi = leaf_hi.astype(jnp.float32)
    acc = jnp.zeros((queries.shape[0], lo.shape[0]), jnp.float32)
    for k in range(queries.shape[1]):
        qk = queries[:, k][:, None]
        g = jnp.maximum(lo[:, k][None, :] - qk, 0.0) + jnp.maximum(
            qk - hi[:, k][None, :], 0.0
        )
        acc = acc + g * g
    return acc


def pair_dist2_ref(queries, leaf_pts, leaf_counts, q_idx, leaf_idx):
    """Reference fused pair distances: plain gathers out of the (d, L, S)
    leaf table, invalid = f32 max."""
    q = queries[q_idx]                        # (P, d)
    pts = jnp.moveaxis(leaf_pts[:, leaf_idx], 0, -1)  # (P, S, d)
    s = leaf_pts.shape[2]
    d2 = jnp.sum((pts - q[:, None, :]) ** 2, axis=2)
    valid = (
        jnp.arange(s, dtype=jnp.int32)[None, :]
        < leaf_counts[leaf_idx][:, None]
    )
    big = jnp.finfo(jnp.float32).max
    return jnp.where(valid, d2, big)

"""Pallas TPU kernel: tiled squared-distance matrix for k-NN scanning.

The query-side hot loop of the paper (leaf scans during k-NN) is dominated
by distance evaluation.  The TPU-native formulation computes

    d2[q, p] = |q|^2 + |p|^2 - 2 q.p

so the inner product lands on the MXU and each (query-tile x point-tile)
block stays resident in VMEM.  Selection (top-k merge) is bandwidth-light
and runs as a plain XLA ``top_k`` over the kernel's output tiles — see
``ops.knn_topk`` for the fused pipeline.

Padding rows (row_id < 0, e.g. FMBI's partial-page sentinels) are masked to
+inf so they never enter a result set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .window_filter import PAIR_ROWS, _pad_pairs


DEFAULT_QT = 256
DEFAULT_PT = 512


def _dist2_kernel(q_ref, p_ref, valid_ref, out_ref):
    q = q_ref[...]                    # (qt, d)
    p = p_ref[...]                    # (pt, d)
    valid = valid_ref[...]            # (pt,)
    qq = jnp.sum(q * q, axis=1)       # (qt,)
    pp = jnp.sum(p * p, axis=1)       # (pt,)
    cross = jax.lax.dot_general(      # MXU: (qt, d) x (pt, d)^T
        q, p, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    d2 = qq[:, None] + pp[None, :] - 2.0 * cross
    d2 = jnp.maximum(d2, 0.0)         # numeric floor
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    out_ref[...] = jnp.where(valid[None, :] > 0, d2, big)


def _gathered_dist2_kernel(q_ref, p_ref, valid_ref, out_ref):
    q = q_ref[...]                    # (1, d)
    p = p_ref[...]                    # (1, pt, d)
    valid = valid_ref[...]            # (1, pt)
    acc = jnp.zeros(p.shape[:2], jnp.float32)
    for k in range(p.shape[2]):       # static unroll over dimensions keeps
        diff = p[..., k] - q[:, k][:, None]   # the working set at one plane
        acc = acc + diff * diff
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    out_ref[...] = jnp.where(valid > 0, acc, big)


@functools.partial(jax.jit, static_argnames=("pt", "interpret"))
def gathered_dist2(
    queries: jnp.ndarray,   # (nq, d) float32
    points: jnp.ndarray,    # (nq, npp, d) float32, npp % pt == 0
    valid: jnp.ndarray,     # (nq, npp) int32: 1 = real candidate, 0 = padding
    *,
    pt: int = DEFAULT_PT,
    interpret: bool = True,
) -> jnp.ndarray:
    """(nq, npp) masked squared distances, per-query gathered layout.

    This is the candidate-leaf scan of the device query engine: each query
    brings its own gathered candidate points (the contents of its closest
    leaves, padded to a fixed shape).  Query-major grid, one query row per
    block — the same layout as ``window_filter.window_count_gathered``.
    Selection (top-k merge) runs as plain XLA ``top_k`` on the output, which
    the consumer fuses.
    """
    nq, npp, d = points.shape
    assert npp % pt == 0, "pad the candidate axis to a tile multiple"
    grid = (nq, npp // pt)
    return pl.pallas_call(
        _gathered_dist2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, pt, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, pt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, pt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, npp), jnp.float32),
        interpret=interpret,
    )(queries, points, valid)


@functools.partial(
    jax.jit, static_argnames=("qt", "pt", "interpret")
)
def pairwise_dist2(
    queries: jnp.ndarray,   # (nq, d) float32, nq % qt == 0
    points: jnp.ndarray,    # (np, d) float32, np % pt == 0
    valid: jnp.ndarray,     # (np,) int32: 1 = real point, 0 = padding
    *,
    qt: int = DEFAULT_QT,
    pt: int = DEFAULT_PT,
    interpret: bool = True,
) -> jnp.ndarray:
    """(nq, np) masked squared distances, computed in VMEM tiles."""
    nq, d = queries.shape
    n_p = points.shape[0]
    assert nq % qt == 0 and n_p % pt == 0, "pad inputs to tile multiples"
    grid = (nq // qt, n_p // pt)
    return pl.pallas_call(
        _dist2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((qt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((pt, d), lambda i, j: (j, 0)),
            pl.BlockSpec((pt,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((qt, pt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, n_p), jnp.float32),
        interpret=interpret,
    )(queries, points, valid)


# --------------------------------------------------------------------------
# second-generation tiled kernels (fused traversal + scan; see ops.py)
# --------------------------------------------------------------------------
def _leaf_mindist_kernel(q_ref, lo_ref, hi_ref, out_ref):
    q = q_ref[...]                          # (d, qt) float32, dimension-major
    lo = lo_ref[...].astype(jnp.float32)    # (lt, d) bounds (f32 or bf16)
    hi = hi_ref[...].astype(jnp.float32)
    acc = jnp.zeros((lo.shape[0], q.shape[1]), jnp.float32)
    for k in range(q.shape[0]):             # static unroll over dimensions:
        qk = q[k:k + 1, :]                  # one (lt, qt) plane at a time,
        g = jnp.maximum(lo[:, k:k + 1] - qk, 0.0) + jnp.maximum(
            qk - hi[:, k:k + 1], 0.0        # built from 2-D slices: leaves
        )                                   # on sublanes, queries on lanes
        acc = acc + g * g
    out_ref[...] = acc.T


@functools.partial(jax.jit, static_argnames=("qt", "lt", "interpret"))
def leaf_mindist_tiled(
    queries: jnp.ndarray,   # (nq, d) float32, nq % qt == 0
    leaf_lo: jnp.ndarray,   # (L, d) leaf MBB lows (f32 or bf16), L % lt == 0
    leaf_hi: jnp.ndarray,   # (L, d)
    *,
    qt: int = 128,
    lt: int = DEFAULT_PT,
    interpret: bool = True,
) -> jnp.ndarray:
    """(nq, L) squared box mindists, VMEM-tiled over both axes.

    The candidate-selection stage of the device k-NN engine.  Bound tiles
    may be bf16 (the compressed-MBB layout): outward rounding only widens a
    box, so a bf16 mindist never exceeds the f32 mindist — candidate
    selection stays a superset-safe underestimate and the exactness
    certificate derived from it is conservative (see queries_jax).

    The (nq, d) query batch enters dimension-major, (d, nq): Mosaic then
    builds each (lt, qt) plane from a leaf column and a query row without
    the 1-D relayouts whose scoped-VMEM stack grows with qt * lt."""
    nq, d = queries.shape
    n_l = leaf_lo.shape[0]
    assert nq % qt == 0 and n_l % lt == 0, "pad inputs to tile multiples"
    grid = (nq // qt, n_l // lt)
    return pl.pallas_call(
        _leaf_mindist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, qt), lambda i, j: (0, i)),
            pl.BlockSpec((lt, d), lambda i, j: (j, 0)),
            pl.BlockSpec((lt, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((qt, lt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, n_l), jnp.float32),
        interpret=interpret,
    )(queries.T, leaf_lo, leaf_hi)


def _pair_dist2_kernel(q_idx_ref, leaf_idx_ref, live_ref, q_ref,  # prefetch
                       pts_ref, out_ref):
    i = pl.program_id(0)
    q = q_idx_ref[i]
    s, d = pts_ref.shape[1], pts_ref.shape[2]
    pt = pts_ref[0].T                       # (d, S): this pair's leaf block
    acc = jnp.zeros((1, s), jnp.float32)
    for k in range(d):                      # static unroll over dimensions
        diff = pt[k:k + 1, :] - q_ref[q * d + k]
        acc = acc + diff * diff
    valid = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1) < live_ref[i]
    big = jnp.asarray(jnp.finfo(jnp.float32).max, jnp.float32)
    out_ref[pl.ds(i % PAIR_ROWS, 1), :] = jnp.where(valid, acc, big)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_dist2(
    queries: jnp.ndarray,     # (nq, d) float32 query points
    leaf_pts: jnp.ndarray,    # (L, S, d) float32 leaf-blocked points
    leaf_counts: jnp.ndarray, # (L,) int32 live slots per block
    q_idx: jnp.ndarray,       # (P,) int32 query of each candidate pair
    leaf_idx: jnp.ndarray,    # (P,) int32 leaf slot of each candidate pair
    *,
    interpret: bool = True,
) -> jnp.ndarray:
    """Fused (query, leaf) candidate scan: (P, S) squared distances.

    Each pair's leaf block streams from the (L, S, d) table straight into
    VMEM through scalar-prefetch BlockSpec index maps — no XLA-materialized
    (P, S, d) gather.  Invalid slots carry float32 max so they sort last in
    the top-k merge.  The block layout follows
    ``window_filter.pair_window_ids``: query points and per-pair live-slot
    counts ride in SMEM, and each step writes one row of a
    ``PAIR_ROWS``-row output tile."""
    n_p = q_idx.shape[0]
    _, s, d = leaf_pts.shape
    q_idx, leaf_idx, live = _pad_pairs(
        q_idx, leaf_idx, leaf_counts[leaf_idx]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(q_idx.shape[0],),
        in_specs=[
            pl.BlockSpec((1, s, d), lambda i, q, l, n, a: (l[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((PAIR_ROWS, s),
                               lambda i, q, l, n, a: (i // PAIR_ROWS, 0)),
    )
    return pl.pallas_call(
        _pair_dist2_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_idx.shape[0], s), jnp.float32),
        interpret=interpret,
    )(q_idx, leaf_idx, live, queries.reshape(-1), leaf_pts)[:n_p]

"""Pallas TPU kernel: tiled window-containment counting for range queries.

The leaf-scan stage of batched window queries reduces to: for each query
box, count the candidate points falling inside it.  On TPU this is a pure
VPU problem — per (query-tile x point-tile) block the 2d coordinate
comparisons and the popcount reduction stay resident in VMEM, and the
per-query partial counts are accumulated across point tiles by revisiting
the output block along the innermost grid dimension (the standard Pallas
reduction idiom: zero on the first visit, ``+=`` afterwards).

Two layouts are provided:

  * :func:`window_count_tiles` — one shared point set scanned by every
    query (the flat leaf table);
  * :func:`window_count_gathered` — each query brings its own gathered
    candidate points, the shape ``core.jax_index.window_count`` produces
    after leaf-level pruning (query-major grid, one query row per block).

Padding points carry ``valid == 0`` and never count, mirroring the row_id
sentinel convention of ``kernels/knn_topk``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_QT = 128
DEFAULT_PT = 512

# VMEM budget for one tiled block's pipelined working set: half of the
# 16 MiB scoped-VMEM limit Mosaic compiles kernels against, leaving the
# rest for the compiler's own scratch
VMEM_TILE_BUDGET = 8 * 1024 * 1024
LANES = 128
# (nt, qt) f32 planes a box-grid kernel body keeps live at once: the
# accumulator plus the per-dimension comparison / gap temporaries
LIVE_PLANES = 4
# pairs per output tile of the pair-scan kernels: one f32/int32 sublane
# tile, the smallest row block Mosaic accepts out of a larger array
PAIR_ROWS = 8


def tile_vmem_bytes(nt: int, qt: int, in_bytes: int = 4) -> int:
    """VMEM one grid step of an (nt x qt) box-grid kernel occupies.

    Every ``(rows, d)`` input tile fills whole 128-lane rows whatever d
    is (a 2-wide minor axis pads 64x), the two bound tiles, the two query
    tiles and the output plane are double-buffered by the pipeline, and
    the body keeps ``LIVE_PLANES`` (nt, qt) f32 planes live."""
    io = 2 * nt * LANES * in_bytes + 2 * qt * LANES * 4 + nt * qt * 4
    return 2 * io + LIVE_PLANES * nt * qt * 4


def vmem_tiles(n: int, q: int, d: int, in_bytes: int = 4,
               budget: int = VMEM_TILE_BUDGET) -> tuple[int, int]:
    """(nt, qt) tile sizes for an (n x q) box-test grid whose per-step
    VMEM (:func:`tile_vmem_bytes`) fits ``budget`` bytes.

    Tiles respect the TPU minimums (8 sublanes x 128 lanes for f32; the
    bf16 bound tiles are cast to f32 in-register, so f32 minimums apply)
    and shrink the box axis first: the query axis is the broadcast axis,
    so a wide qt amortizes bound loads across more queries.  ``n`` and
    ``d`` do not change the tile: the lane padding makes a tile's bytes
    independent of d."""
    qt = min(128, _pow2_ceil(q))
    nt = 1024
    while nt > 8 and tile_vmem_bytes(nt, qt, in_bytes) > budget:
        nt //= 2
    return max(nt, 8), max(qt, 8)


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def _tiles_kernel(lo_ref, hi_ref, p_ref, valid_ref, out_ref):
    j = pl.program_id(1)
    lo = lo_ref[...]                  # (qt, d)
    hi = hi_ref[...]                  # (qt, d)
    p = p_ref[...]                    # (pt, d)
    valid = valid_ref[...]            # (pt,)
    acc = jnp.broadcast_to(valid[None, :] > 0, (lo.shape[0], p.shape[0]))
    for k in range(p.shape[1]):       # static unroll over dimensions keeps
        pk = p[:, k][None, :]         # the working set at one (qt, pt) plane
        acc = acc & (pk >= lo[:, k][:, None]) & (pk <= hi[:, k][:, None])
    cnt = jnp.sum(acc.astype(jnp.int32), axis=1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += cnt


@functools.partial(jax.jit, static_argnames=("qt", "pt", "interpret"))
def window_count_tiles(
    lo: jnp.ndarray,        # (nq, d) float32, nq % qt == 0
    hi: jnp.ndarray,        # (nq, d) float32
    points: jnp.ndarray,    # (np, d) float32, np % pt == 0
    valid: jnp.ndarray,     # (np,) int32: 1 = real point, 0 = padding
    *,
    qt: int = DEFAULT_QT,
    pt: int = DEFAULT_PT,
    interpret: bool = True,
) -> jnp.ndarray:
    """(nq,) in-window point counts over one shared point table."""
    nq, d = lo.shape
    n_p = points.shape[0]
    assert nq % qt == 0 and n_p % pt == 0, "pad inputs to tile multiples"
    grid = (nq // qt, n_p // pt)
    return pl.pallas_call(
        _tiles_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((qt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((qt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((pt, d), lambda i, j: (j, 0)),
            pl.BlockSpec((pt,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((qt,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((nq,), jnp.int32),
        interpret=interpret,
    )(lo, hi, points, valid)


def _gathered_mask_kernel(lo_ref, hi_ref, p_ref, valid_ref, out_ref):
    lo = lo_ref[...]                  # (1, d)
    hi = hi_ref[...]                  # (1, d)
    p = p_ref[...]                    # (1, pt, d)
    valid = valid_ref[...]            # (1, pt)
    acc = valid > 0
    for k in range(p.shape[2]):
        pk = p[..., k]                # (1, pt)
        acc = acc & (pk >= lo[:, k][:, None]) & (pk <= hi[:, k][:, None])
    out_ref[...] = acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("pt", "interpret"))
def window_mask_gathered(
    lo: jnp.ndarray,        # (nq, d) float32
    hi: jnp.ndarray,        # (nq, d) float32
    points: jnp.ndarray,    # (nq, npp, d) float32, npp % pt == 0
    valid: jnp.ndarray,     # (nq, npp) int32
    *,
    pt: int = DEFAULT_PT,
    interpret: bool = True,
) -> jnp.ndarray:
    """(nq, npp) per-candidate containment mask (1 = inside the query box).

    The *collection* variant of :func:`window_count_gathered`: instead of
    reducing to a count it keeps the full mask so the device query engine
    can pack the qualifying candidate ids into its fixed-shape result
    buffer.  Pure map over (query, candidate-tile) blocks — no revisit
    accumulation is needed.
    """
    nq, npp, d = points.shape
    assert npp % pt == 0, "pad the candidate axis to a tile multiple"
    grid = (nq, npp // pt)
    return pl.pallas_call(
        _gathered_mask_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, pt, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, pt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, pt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, npp), jnp.int32),
        interpret=interpret,
    )(lo, hi, points, valid)


def _gathered_kernel(lo_ref, hi_ref, p_ref, valid_ref, out_ref):
    j = pl.program_id(1)
    lo = lo_ref[...]                  # (1, d)
    hi = hi_ref[...]                  # (1, d)
    p = p_ref[...]                    # (1, pt, d)
    valid = valid_ref[...]            # (1, pt)
    acc = valid > 0
    for k in range(p.shape[2]):
        pk = p[..., k]                # (1, pt)
        acc = acc & (pk >= lo[:, k][:, None]) & (pk <= hi[:, k][:, None])
    cnt = jnp.sum(acc.astype(jnp.int32), axis=1)  # (1,)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += cnt


@functools.partial(jax.jit, static_argnames=("pt", "interpret"))
def window_count_gathered(
    lo: jnp.ndarray,        # (nq, d) float32
    hi: jnp.ndarray,        # (nq, d) float32
    points: jnp.ndarray,    # (nq, npp, d) float32, npp % pt == 0
    valid: jnp.ndarray,     # (nq, npp) int32
    *,
    pt: int = DEFAULT_PT,
    interpret: bool = True,
) -> jnp.ndarray:
    """(nq,) in-window counts; each query scans its own gathered points."""
    nq, npp, d = points.shape
    assert npp % pt == 0, "pad the candidate axis to a tile multiple"
    grid = (nq, npp // pt)
    return pl.pallas_call(
        _gathered_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, pt, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, pt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((nq,), jnp.int32),
        interpret=interpret,
    )(lo, hi, points, valid)


# --------------------------------------------------------------------------
# second-generation tiled kernels (fused traversal + scan; see ops.py)
# --------------------------------------------------------------------------
def _box_hits_kernel(lo_ref, hi_ref, qlo_ref, qhi_ref, out_ref):
    lo = lo_ref[...].astype(jnp.float32)    # (nt, d) box lows (f32 or bf16)
    hi = hi_ref[...].astype(jnp.float32)    # (nt, d)
    qlo = qlo_ref[...]                      # (qt, d) query lows, f32
    qhi = qhi_ref[...]                      # (qt, d)
    acc = None
    for k in range(lo.shape[1]):            # static unroll over dimensions:
        h = (lo[:, k][:, None] <= qhi[:, k][None, :]) & (
            hi[:, k][:, None] >= qlo[:, k][None, :]
        )                                   # one (nt, qt) plane at a time
        acc = h if acc is None else acc & h
    out_ref[...] = acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("nt", "qt", "interpret"))
def box_hits_tiled(
    lo: jnp.ndarray,        # (n, d) box lows (f32, or outward-rounded bf16)
    hi: jnp.ndarray,        # (n, d)
    qlo: jnp.ndarray,       # (nq, d) float32 query window lows, nq % qt == 0
    qhi: jnp.ndarray,       # (nq, d)
    *,
    nt: int = DEFAULT_PT,
    qt: int = DEFAULT_QT,
    interpret: bool = True,
) -> jnp.ndarray:
    """(n, nq) int32 box-intersection mask, VMEM-tiled over both axes.

    The per-level frontier box test of the device query engine: one level
    block's MBB columns against the whole query batch.  Bound tiles may be
    bf16 (the compressed-MBB layout) — they are widened to f32 in-register,
    so only the *storage* (and therefore the HBM traffic) is halved; the
    comparison itself is exact on the outward-rounded bounds, which keeps
    the hit mask a superset of the f32 mask (never a false negative)."""
    n, d = lo.shape
    nq = qlo.shape[0]
    assert n % nt == 0 and nq % qt == 0, "pad inputs to tile multiples"
    grid = (n // nt, nq // qt)
    return pl.pallas_call(
        _box_hits_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((nt, d), lambda i, j: (i, 0)),
            pl.BlockSpec((qt, d), lambda i, j: (j, 0)),
            pl.BlockSpec((qt, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((nt, qt), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, nq), jnp.int32),
        interpret=interpret,
    )(lo, hi, qlo, qhi)


def _pad_pairs(*cols):
    """Pad per-pair index columns to a ``PAIR_ROWS`` multiple with
    zeros; a padding pair gets zero live slots, so it matches nothing."""
    n_p = cols[0].shape[0]
    extra = -(-n_p // PAIR_ROWS) * PAIR_ROWS - n_p
    return [jnp.pad(c.astype(jnp.int32), (0, extra)) for c in cols]


def _pair_window_ids_kernel(
    q_idx_ref, leaf_idx_ref, live_ref, qlo_ref, qhi_ref,  # scalar prefetch
    pts_ref, ids_ref, out_ref,
):
    i = pl.program_id(0)
    q = q_idx_ref[i]
    s, d = pts_ref.shape[1], pts_ref.shape[2]
    pt = pts_ref[0].T                       # (d, S): this pair's leaf block
    # the leaf's id row out of its PAIR_ROWS-row tile of the id table
    ids = ids_ref[pl.ds(leaf_idx_ref[i] % PAIR_ROWS, 1), :]      # (1, S)
    acc = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1) < live_ref[i]
    for k in range(d):                      # exact containment on f32 points
        pk = pt[k:k + 1, :]                 # (1, S)
        acc = acc & (pk >= qlo_ref[q * d + k]) & (pk <= qhi_ref[q * d + k])
    out_ref[pl.ds(i % PAIR_ROWS, 1), :] = jnp.where(acc, ids, -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pair_window_ids(
    qlo: jnp.ndarray,       # (nq, d) float32 query window lows
    qhi: jnp.ndarray,       # (nq, d)
    leaf_lo: jnp.ndarray,   # (L, d) exact f32 leaf MBB lows
    leaf_hi: jnp.ndarray,   # (L, d)
    leaf_pts: jnp.ndarray,  # (L, S, d) float32 leaf-blocked points
    leaf_ids: jnp.ndarray,  # (L, S) int32 dataset rows, pad = -1
    leaf_counts: jnp.ndarray,  # (L,) int32 live slots per block
    q_idx: jnp.ndarray,     # (P,) int32 query of each candidate pair
    leaf_idx: jnp.ndarray,  # (P,) int32 leaf slot of each candidate pair
    pair_valid: jnp.ndarray,  # (P,) int32 padding mask
    *,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused (query, leaf) pair scan: ``(ids_or (P, S), counts (P,))``.

    ``ids_or[p, s]`` is the dataset row of slot ``s`` of pair ``p``'s leaf
    when the point lies inside the pair's query window, else ``-1``; the
    device packing stage compacts the non-negatives.  The pair's leaf block
    is pulled straight from the (L, S, d) leaf table into VMEM through a
    scalar-prefetch BlockSpec index map — the gather that the
    first-generation path materialized as an XLA (P, S, d) temporary is
    fused into the kernel's block streaming.

    Mosaic only blocks the last two axes of an array in whole (8, 128)
    tiles, so nothing here is a one-row block of a larger array: the
    query boxes ride in SMEM with the other per-pair scalars, the id row
    is read out of its ``PAIR_ROWS``-row tile, and each grid step writes
    one row of a ``PAIR_ROWS``-row output tile that stays resident until
    the pair index leaves it.  The certified f32 re-check of each pair's
    exact leaf box (a pair surfaced by the widened bf16 frontier whose
    exact MBB misses the window is dropped) and the padding mask fold into
    the pair's live-slot count before the kernel runs."""
    n_p = q_idx.shape[0]
    _, s, d = leaf_pts.shape
    box_ok = jnp.all(
        (leaf_lo[leaf_idx].astype(jnp.float32) <= qhi[q_idx])
        & (leaf_hi[leaf_idx].astype(jnp.float32) >= qlo[q_idx]),
        axis=1,
    )
    live = jnp.where(box_ok & (pair_valid > 0), leaf_counts[leaf_idx], 0)
    q_idx, leaf_idx, live = _pad_pairs(q_idx, leaf_idx, live)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(q_idx.shape[0],),
        in_specs=[
            pl.BlockSpec((1, s, d), lambda i, q, l, n, a, b: (l[i], 0, 0)),
            pl.BlockSpec((PAIR_ROWS, s),
                         lambda i, q, l, n, a, b: (l[i] // PAIR_ROWS, 0)),
        ],
        out_specs=pl.BlockSpec((PAIR_ROWS, s),
                               lambda i, q, l, n, a, b: (i // PAIR_ROWS, 0)),
    )
    ids_or = pl.pallas_call(
        _pair_window_ids_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_idx.shape[0], s), jnp.int32),
        interpret=interpret,
    )(
        q_idx, leaf_idx, live, qlo.reshape(-1), qhi.reshape(-1),
        leaf_pts, leaf_ids,
    )[:n_p]
    # every live slot carries a non-negative dataset row
    return ids_or, jnp.sum((ids_or >= 0).astype(jnp.int32), axis=1)

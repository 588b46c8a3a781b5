"""Public jit'd wrappers over the Pallas kernels.

``interpret`` defaults to the backend: Pallas interprets the kernel body on
CPU (validation mode) and compiles to Mosaic on TPU.  All wrappers handle
padding to tile multiples so callers can pass ragged sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import knn_topk as _knn
from . import partition_assign as _pa
from . import ref
from . import window_filter as _wf


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def interpret_default() -> bool:
    """Interpret exactly when no TPU is attached: on a TPU backend the
    kernels always compile to Mosaic."""
    return not _on_tpu()


def _pad_rows(x, mult, fill):
    n = x.shape[0]
    n_pad = -(-n // mult) * mult
    if n_pad == n:
        return x, n
    pad = jnp.full((n_pad - n,) + x.shape[1:], fill, dtype=x.dtype)
    return jnp.concatenate([x, pad]), n


def partition_assign(points, split_dim, split_val, *, levels: int,
                     tile: int = _pa.DEFAULT_TILE,
                     interpret: bool | None = None):
    """Leaf/subspace id per point via the Pallas routing kernel."""
    if interpret is None:
        interpret = interpret_default()
    pts, n = _pad_rows(jnp.asarray(points, jnp.float32), tile, 0.0)
    out = _pa.partition_assign(
        pts, split_dim, split_val, levels=levels, tile=tile,
        interpret=interpret,
    )
    return out[:n]


def pairwise_dist2(queries, points, valid=None, *, qt=_knn.DEFAULT_QT,
                   pt=_knn.DEFAULT_PT, interpret: bool | None = None):
    """Masked (nq, np) squared distances via the Pallas tile kernel."""
    if interpret is None:
        interpret = interpret_default()
    q = jnp.asarray(queries, jnp.float32)
    p = jnp.asarray(points, jnp.float32)
    if valid is None:
        valid = jnp.ones(p.shape[0], jnp.int32)
    qp, nq = _pad_rows(q, qt, 0.0)
    pp, n_p = _pad_rows(p, pt, 0.0)
    vp, _ = _pad_rows(jnp.asarray(valid, jnp.int32), pt, 0)
    d2 = _knn.pairwise_dist2(qp, pp, vp, qt=qt, pt=pt, interpret=interpret)
    return d2[:nq, :n_p]


# ceiling on how many distance-matrix elements a single knn_topk dispatch
# may materialize (fp32: 64 MiB); larger batches stream in query chunks
KNN_MAX_ELEMS = 16 * 1024 * 1024


def knn_topk(queries, points, k: int, valid=None, *,
             query_chunk: int | None = None, **kw):
    """k nearest points per query: Pallas distance tiles + XLA top-k merge.

    Returns (indices (nq, k), dists_sq (nq, k)).  The selection stage is a
    plain ``top_k`` because it is bandwidth-trivial next to the distance
    matrix; on TPU the distance tiles stream from the kernel while top_k
    consumes them (XLA fuses the consumer).

    Memory is capped: when the full (nq, np) distance matrix would exceed
    ``KNN_MAX_ELEMS`` elements, the query axis is processed in chunks (of
    ``query_chunk`` rows when given, else sized to the cap) so only one
    chunk's distances are live at a time."""
    nq = queries.shape[0]
    n_p = points.shape[0]
    if query_chunk is None and nq * max(n_p, 1) > KNN_MAX_ELEMS:
        query_chunk = max(KNN_MAX_ELEMS // max(n_p, 1), 1)
    if query_chunk is None or query_chunk >= nq:
        d2 = pairwise_dist2(queries, points, valid=valid, **kw)
        neg, idx = jax.lax.top_k(-d2, k)
        return idx, -neg
    idx_parts, dist_parts = [], []
    for start in range(0, nq, query_chunk):
        d2 = pairwise_dist2(
            queries[start : start + query_chunk], points, valid=valid, **kw
        )
        neg, idx = jax.lax.top_k(-d2, k)
        idx_parts.append(idx)
        dist_parts.append(-neg)
    return jnp.concatenate(idx_parts), jnp.concatenate(dist_parts)


def window_count(lo, hi, points, valid=None, *, qt=_wf.DEFAULT_QT,
                 pt=_wf.DEFAULT_PT, interpret: bool | None = None):
    """In-window point counts per query box via the Pallas tile kernel."""
    if interpret is None:
        interpret = interpret_default()
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    p = jnp.asarray(points, jnp.float32)
    if valid is None:
        valid = jnp.ones(p.shape[0], jnp.int32)
    # query padding boxes are inverted (lo > hi): they can never match
    lo_p, nq = _pad_rows(lo, qt, 1.0)
    hi_p, _ = _pad_rows(hi, qt, 0.0)
    pp, _ = _pad_rows(p, pt, 0.0)
    vp, _ = _pad_rows(jnp.asarray(valid, jnp.int32), pt, 0)
    cnt = _wf.window_count_tiles(
        lo_p, hi_p, pp, vp, qt=qt, pt=pt, interpret=interpret
    )
    return cnt[:nq]


def window_count_gathered(lo, hi, points, valid, *, pt=_wf.DEFAULT_PT,
                          interpret: bool | None = None):
    """Per-query gathered layout: ``points`` is (nq, npp, d) with its own
    validity mask; the candidate axis is padded to a tile multiple here."""
    if interpret is None:
        interpret = interpret_default()
    lo = jnp.asarray(lo, jnp.float32)
    hi = jnp.asarray(hi, jnp.float32)
    p = jnp.asarray(points, jnp.float32)
    v = jnp.asarray(valid, jnp.int32)
    npp = p.shape[1]
    npp_pad = -(-max(npp, 1) // pt) * pt
    if npp_pad != npp:
        p = jnp.pad(p, ((0, 0), (0, npp_pad - npp), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, npp_pad - npp)))
    return _wf.window_count_gathered(lo, hi, p, v, pt=pt, interpret=interpret)


def _pad_gathered(lo, hi, points, valid, pt):
    """Shared prep for the per-query gathered kernels: cast + pad the
    candidate axis to a tile multiple."""
    lo = jnp.asarray(lo, jnp.float32)
    hi = None if hi is None else jnp.asarray(hi, jnp.float32)
    p = jnp.asarray(points, jnp.float32)
    v = jnp.asarray(valid, jnp.int32)
    npp = p.shape[1]
    npp_pad = -(-max(npp, 1) // pt) * pt
    if npp_pad != npp:
        p = jnp.pad(p, ((0, 0), (0, npp_pad - npp), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, npp_pad - npp)))
    return lo, hi, p, v, npp


def window_mask_gathered(lo, hi, points, valid, *, pt=_wf.DEFAULT_PT,
                         interpret: bool | None = None):
    """Per-candidate containment mask (nq, npp) for the gathered layout —
    the collection stage of the device window engine."""
    if interpret is None:
        interpret = interpret_default()
    lo, hi, p, v, npp = _pad_gathered(lo, hi, points, valid, pt)
    out = _wf.window_mask_gathered(lo, hi, p, v, pt=pt, interpret=interpret)
    return out[:, :npp]


def gathered_dist2(queries, points, valid, *, pt=_knn.DEFAULT_PT,
                   interpret: bool | None = None):
    """Per-query gathered squared distances (nq, npp) — the candidate-leaf
    scan of the device k-NN engine (invalid slots carry float32 max)."""
    if interpret is None:
        interpret = interpret_default()
    q, _, p, v, npp = _pad_gathered(queries, None, points, valid, pt)
    out = _knn.gathered_dist2(q, p, v, pt=pt, interpret=interpret)
    return out[:, :npp]


# re-export oracles for test convenience
partition_assign_ref = ref.partition_assign_ref
pairwise_dist2_ref = ref.pairwise_dist2_ref
knn_topk_ref = ref.knn_topk_ref
window_count_ref = ref.window_count_ref
window_count_gathered_ref = ref.window_count_gathered_ref
window_mask_gathered_ref = ref.window_mask_gathered_ref
gathered_dist2_ref = ref.gathered_dist2_ref


def compiled_supported() -> bool:
    """True when ``interpret=False`` pallas_call can actually compile on
    the attached backend (Mosaic = TPU only; the CPU backend raises)."""
    return _on_tpu()


# --------------------------------------------------------------------------
# second-generation fused/tiled wrappers (the queries_jax hot path)
# --------------------------------------------------------------------------
def box_hits_tiled(lo, hi, qlo, qhi, *, nt: int | None = None,
                   qt: int | None = None, interpret: bool | None = None):
    """(n, nq) box-intersection mask via the VMEM-tiled kernel.

    ``lo``/``hi`` may be bf16 (compressed-MBB storage).  Padding boxes are
    inverted (lo = +max, hi = -max) and padding query windows likewise, so
    neither can ever intersect; both axes are sliced back."""
    if interpret is None:
        interpret = interpret_default()
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi)
    qlo = jnp.asarray(qlo, jnp.float32)
    qhi = jnp.asarray(qhi, jnp.float32)
    n, d = lo.shape
    if nt is None or qt is None:
        nt0, qt0 = _wf.vmem_tiles(n, qlo.shape[0], d,
                                  in_bytes=lo.dtype.itemsize)
        nt = nt if nt is not None else nt0
        qt = qt if qt is not None else qt0
    big = float(jnp.finfo(jnp.float32).max)
    lo_p, n0 = _pad_rows(lo, nt, big)
    hi_p, _ = _pad_rows(hi, nt, -big)
    qlo_p, q0 = _pad_rows(qlo, qt, big)
    qhi_p, _ = _pad_rows(qhi, qt, -big)
    out = _wf.box_hits_tiled(lo_p, hi_p, qlo_p, qhi_p, nt=nt, qt=qt,
                             interpret=interpret)
    return out[:n0, :q0]


def pair_window_ids(qlo, qhi, leaf_lo, leaf_hi, leaf_pts, leaf_ids,
                    leaf_counts, q_idx, leaf_idx, pair_valid, *,
                    interpret: bool | None = None):
    """Fused (query, leaf) pair window scan: ``(ids_or (P, S), counts)``.

    One grid step per pair; the pair's leaf block is gathered into VMEM by
    the scalar-prefetch index maps, so no (P, S, d) temporary exists."""
    if interpret is None:
        interpret = interpret_default()
    return _wf.pair_window_ids(
        jnp.asarray(qlo, jnp.float32), jnp.asarray(qhi, jnp.float32),
        leaf_lo, leaf_hi, leaf_pts, leaf_ids, leaf_counts,
        q_idx, leaf_idx, pair_valid, interpret=interpret,
    )


def leaf_mindist_tiled(queries, leaf_lo, leaf_hi, *, qt: int = 128,
                       lt: int | None = None,
                       interpret: bool | None = None):
    """(nq, L) squared box mindists via the VMEM-tiled kernel.

    ``leaf_lo``/``leaf_hi`` may be bf16.  Padding leaves carry degenerate
    far-away boxes (lo = hi = +max) whose mindist overflows to +inf, so
    they can never be selected; both axes are sliced back."""
    if interpret is None:
        interpret = interpret_default()
    q = jnp.asarray(queries, jnp.float32)
    lo = jnp.asarray(leaf_lo)
    hi = jnp.asarray(leaf_hi)
    if lt is None:
        nt0, _ = _wf.vmem_tiles(lo.shape[0], q.shape[0], lo.shape[1],
                                in_bytes=lo.dtype.itemsize)
        lt = nt0
    big = float(jnp.finfo(jnp.float32).max)
    qp, nq = _pad_rows(q, qt, 0.0)
    lo_p, n_l = _pad_rows(lo, lt, big)
    hi_p, _ = _pad_rows(hi, lt, big)
    out = _knn.leaf_mindist_tiled(qp, lo_p, hi_p, qt=qt, lt=lt,
                                  interpret=interpret)
    return out[:nq, :n_l]


def pair_dist2(queries, leaf_pts, leaf_counts, q_idx, leaf_idx, *,
               interpret: bool | None = None):
    """Fused (query, leaf) candidate distances: (P, S), invalid = f32 max."""
    if interpret is None:
        interpret = interpret_default()
    return _knn.pair_dist2(
        jnp.asarray(queries, jnp.float32), leaf_pts, leaf_counts,
        q_idx, leaf_idx, interpret=interpret,
    )


box_hits_tiled_ref = ref.box_hits_tiled_ref
pair_window_ids_ref = ref.pair_window_ids_ref
leaf_mindist_ref = ref.leaf_mindist_ref
pair_dist2_ref = ref.pair_dist2_ref

"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jax_index
from repro.kernels import ops


def _index(n, d, levels, seed):
    rng = np.random.default_rng(seed)
    pts = rng.random((n, d)).astype(np.float32)
    padded, ids = jax_index.pad_points(pts, levels)
    return pts, jax_index.build(
        jnp.asarray(padded), levels, jnp.asarray(ids, jnp.int32)
    )


@pytest.mark.parametrize("d", [2, 3, 5])
@pytest.mark.parametrize("levels", [3, 6])
@pytest.mark.parametrize("tile", [64, 256])
def test_partition_assign_matches_ref(d, levels, tile):
    pts, idx = _index(1 << (levels + 3), d, levels, seed=d * 10 + levels)
    rng = np.random.default_rng(99)
    q = rng.random((777, d)).astype(np.float32)  # ragged: exercises padding
    got = ops.partition_assign(
        q, idx.split_dim, idx.split_val, levels=levels, tile=tile
    )
    want = ops.partition_assign_ref(
        jnp.asarray(q), idx.split_dim, idx.split_val, levels=levels
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("d", [2, 4, 8])
@pytest.mark.parametrize("qt,pt", [(64, 128), (128, 512)])
def test_pairwise_dist2_matches_ref(d, qt, pt):
    rng = np.random.default_rng(d)
    q = rng.normal(0, 1, (200, d)).astype(np.float32)
    p = rng.normal(0, 1, (900, d)).astype(np.float32)
    valid = (rng.random(900) > 0.1).astype(np.int32)
    got = ops.pairwise_dist2(q, p, valid, qt=qt, pt=pt)
    want = ops.pairwise_dist2_ref(
        jnp.asarray(q), jnp.asarray(p), jnp.asarray(valid)
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("k", [1, 8, 33])
def test_knn_topk_matches_ref(k):
    rng = np.random.default_rng(k)
    q = rng.normal(0, 1, (64, 3)).astype(np.float32)
    p = rng.normal(0, 1, (512, 3)).astype(np.float32)
    valid = np.ones(512, np.int32)
    valid[500:] = 0
    gi, gd = ops.knn_topk(q, p, k, valid=valid, qt=64, pt=128)
    ri, rd = ops.knn_topk_ref(
        jnp.asarray(q), jnp.asarray(p), jnp.asarray(valid), k
    )
    np.testing.assert_allclose(
        np.sort(np.asarray(gd)), np.sort(np.asarray(rd)), rtol=1e-4,
        atol=1e-6,
    )
    # masked points never appear
    assert np.all(np.asarray(gi) < 500)


def test_kernel_route_agrees_with_index_route():
    pts, idx = _index(2048, 3, 5, seed=4)
    q = np.random.default_rng(1).random((512, 3)).astype(np.float32)
    a = ops.partition_assign(q, idx.split_dim, idx.split_val, levels=5)
    b = jax_index.route(idx, jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("d", [2, 4])
@pytest.mark.parametrize("qt,pt", [(64, 128), (128, 512)])
def test_window_count_tiles_matches_ref(d, qt, pt):
    rng = np.random.default_rng(d * 7 + qt)
    lo = rng.random((150, d)).astype(np.float32) * 0.8  # ragged: padding
    hi = lo + rng.uniform(0.05, 0.4, (150, d)).astype(np.float32)
    p = rng.random((900, d)).astype(np.float32)
    valid = (rng.random(900) > 0.15).astype(np.int32)
    got = ops.window_count(lo, hi, p, valid, qt=qt, pt=pt)
    want = ops.window_count_ref(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(p), jnp.asarray(valid)
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).sum() > 0  # non-degenerate case


@pytest.mark.parametrize("pt", [128, 512])
def test_window_count_gathered_matches_ref(pt):
    rng = np.random.default_rng(pt)
    nq, npp, d = 13, 300, 3  # ragged candidate axis: exercises padding
    lo = rng.random((nq, d)).astype(np.float32) * 0.7
    hi = lo + 0.3
    p = rng.random((nq, npp, d)).astype(np.float32)
    valid = (rng.random((nq, npp)) > 0.1).astype(np.int32)
    got = ops.window_count_gathered(lo, hi, p, valid, pt=pt)
    want = ops.window_count_gathered_ref(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(p), jnp.asarray(valid)
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pt", [128, 512])
def test_window_mask_gathered_matches_ref(pt):
    """Collection variant: the per-candidate mask, not just its sum."""
    rng = np.random.default_rng(pt + 1)
    nq, npp, d = 11, 300, 2  # ragged candidate axis: exercises padding
    lo = rng.random((nq, d)).astype(np.float32) * 0.7
    hi = lo + 0.3
    p = rng.random((nq, npp, d)).astype(np.float32)
    valid = (rng.random((nq, npp)) > 0.1).astype(np.int32)
    got = ops.window_mask_gathered(lo, hi, p, valid, pt=pt)
    want = ops.window_mask_gathered_ref(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(p), jnp.asarray(valid)
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # mask sums agree with the counting kernel
    cnt = ops.window_count_gathered(lo, hi, p, valid, pt=pt)
    np.testing.assert_array_equal(
        np.asarray(got).sum(axis=1), np.asarray(cnt)
    )


@pytest.mark.parametrize("pt", [128, 512])
@pytest.mark.parametrize("d", [2, 5])
def test_gathered_dist2_matches_ref(pt, d):
    rng = np.random.default_rng(pt * 3 + d)
    nq, npp = 9, 275  # ragged candidate axis: exercises padding
    q = rng.normal(0, 1, (nq, d)).astype(np.float32)
    p = rng.normal(0, 1, (nq, npp, d)).astype(np.float32)
    valid = (rng.random((nq, npp)) > 0.2).astype(np.int32)
    got = ops.gathered_dist2(q, p, valid, pt=pt)
    want = ops.gathered_dist2_ref(
        jnp.asarray(q), jnp.asarray(p), jnp.asarray(valid)
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )
    big = np.finfo(np.float32).max
    assert np.all(np.asarray(got)[valid == 0] == big)


def test_knn_topk_query_chunking_matches_unchunked():
    """The memory-capped (chunked) path returns the unchunked answer."""
    rng = np.random.default_rng(3)
    q = rng.normal(0, 1, (70, 3)).astype(np.float32)
    p = rng.normal(0, 1, (256, 3)).astype(np.float32)
    gi, gd = ops.knn_topk(q, p, 5, qt=64, pt=128)
    ci, cd = ops.knn_topk(q, p, 5, qt=64, pt=128, query_chunk=16)
    np.testing.assert_allclose(np.asarray(cd), np.asarray(gd), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(gi))


def test_dist2_dtype_f32_output_for_bf16_inputs():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(0, 1, (64, 4)), jnp.bfloat16)
    p = jnp.asarray(rng.normal(0, 1, (128, 4)), jnp.bfloat16)
    out = ops.pairwise_dist2(q, p, qt=64, pt=128)
    assert out.dtype == jnp.float32


# --------------------------------------------------------------------------
# PR-7 fused tiled kernels: frontier box test + pair-scan family
# --------------------------------------------------------------------------
@pytest.mark.parametrize("d", [2, 4])
@pytest.mark.parametrize("box_dtype", [jnp.float32, jnp.bfloat16])
def test_box_hits_tiled_matches_ref(d, box_dtype):
    rng = np.random.default_rng(d * 13)
    n, nq = 150, 77  # both axes ragged: exercises inverted-box padding
    lo = rng.random((n, d)).astype(np.float32) * 0.8
    hi = lo + rng.uniform(0.02, 0.3, (n, d)).astype(np.float32)
    qlo = rng.random((nq, d)).astype(np.float32) * 0.8
    qhi = qlo + rng.uniform(0.02, 0.3, (nq, d)).astype(np.float32)
    lo_c, hi_c = jnp.asarray(lo, box_dtype), jnp.asarray(hi, box_dtype)
    got = ops.box_hits_tiled(lo_c, hi_c, qlo, qhi)
    want = ops.box_hits_tiled_ref(
        lo_c, hi_c, jnp.asarray(qlo), jnp.asarray(qhi)
    )
    assert got.shape == (n, nq)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).sum() > 0


def _pair_workload(seed, p=37, n_l=12, s=64, d=3):
    """A (query, leaf) pair workload with ragged leaves and padding pairs."""
    rng = np.random.default_rng(seed)
    leaf_pts = rng.random((n_l, s, d)).astype(np.float32)
    leaf_counts = rng.integers(1, s + 1, n_l).astype(np.int32)
    big = np.finfo(np.float32).max
    for l in range(n_l):  # dead slots: sentinel coords + id -1
        leaf_pts[l, leaf_counts[l]:] = big
    leaf_ids = np.arange(n_l * s, dtype=np.int32).reshape(n_l, s)
    leaf_ids[np.arange(s)[None, :] >= leaf_counts[:, None]] = -1
    leaf_lo = leaf_pts.min(axis=1)
    leaf_hi = np.where(
        np.arange(s)[None, :, None] < leaf_counts[:, None, None],
        leaf_pts, -big,
    ).max(axis=1)
    nq = 9
    qlo = rng.random((nq, d)).astype(np.float32) * 0.6
    qhi = qlo + 0.35
    q_idx = rng.integers(0, nq, p).astype(np.int32)
    leaf_idx = rng.integers(0, n_l, p).astype(np.int32)
    pair_valid = (rng.random(p) > 0.2).astype(np.int32)
    return (qlo, qhi, leaf_lo, leaf_hi, leaf_pts, leaf_ids, leaf_counts,
            q_idx, leaf_idx, pair_valid)


@pytest.mark.parametrize("seed", [0, 7])
def test_pair_window_ids_matches_ref(seed):
    w = _pair_workload(seed)
    gi, gc = ops.pair_window_ids(*[jnp.asarray(x) for x in w])
    ri, rc = ops.pair_window_ids_ref(*[jnp.asarray(x) for x in w])
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(gc), np.asarray(rc))
    # invalid pairs contribute nothing
    pv = w[-1]
    assert np.all(np.asarray(gi)[pv == 0] == -1)
    assert np.all(np.asarray(gc)[pv == 0] == 0)
    # counts agree with the id matrix
    np.testing.assert_array_equal(
        (np.asarray(gi) >= 0).sum(axis=1), np.asarray(gc)
    )


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("box_dtype", [jnp.float32, jnp.bfloat16])
def test_leaf_mindist_tiled_matches_ref(d, box_dtype):
    rng = np.random.default_rng(d * 31)
    nq, n_l = 21, 90  # ragged axes: degenerate far-box padding
    q = rng.random((nq, d)).astype(np.float32)
    lo = rng.random((n_l, d)).astype(np.float32) * 0.8
    hi = lo + rng.uniform(0.02, 0.2, (n_l, d)).astype(np.float32)
    lo_c, hi_c = jnp.asarray(lo, box_dtype), jnp.asarray(hi, box_dtype)
    got = ops.leaf_mindist_tiled(q, lo_c, hi_c)
    want = ops.leaf_mindist_ref(jnp.asarray(q), lo_c, hi_c)
    assert got.shape == (nq, n_l)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=0
    )
    # inside-the-box queries have exactly zero mindist
    assert (np.asarray(got) == 0).any()


@pytest.mark.parametrize("seed", [1, 5])
def test_pair_dist2_matches_ref(seed):
    (qlo, _, _, _, leaf_pts, _, leaf_counts, q_idx, leaf_idx,
     _) = _pair_workload(seed)
    q = qlo  # any query coordinates do
    got = ops.pair_dist2(q, leaf_pts, leaf_counts, q_idx, leaf_idx)
    want = ops.pair_dist2_ref(
        jnp.asarray(q), jnp.asarray(leaf_pts), jnp.asarray(leaf_counts),
        jnp.asarray(q_idx), jnp.asarray(leaf_idx),
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=0
    )
    # dead slots carry the f32-max sentinel, never a finite distance
    s = leaf_pts.shape[1]
    dead = np.arange(s)[None, :] >= leaf_counts[leaf_idx][:, None]
    assert np.all(np.asarray(got)[dead] == np.finfo(np.float32).max)


def test_box_hits_tiled_compiled_matches_interpret():
    """Interpret mode is the oracle everywhere; on a TPU backend the
    compiled (Mosaic) lowering must agree with it bit-for-bit.  On CPU
    the compiled leg is a no-op and the interpret-vs-ref assertion
    carries the test."""
    rng = np.random.default_rng(0)
    lo = rng.random((200, 3)).astype(np.float32) * 0.8
    hi = lo + 0.1
    qlo = rng.random((64, 3)).astype(np.float32) * 0.8
    qhi = qlo + 0.1
    b = ops.box_hits_tiled(lo, hi, qlo, qhi, interpret=True)
    want = ops.box_hits_tiled_ref(
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(qlo),
        jnp.asarray(qhi),
    )
    np.testing.assert_array_equal(np.asarray(b), np.asarray(want))
    if ops.compiled_supported():
        a = ops.box_hits_tiled(lo, hi, qlo, qhi, interpret=False)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_vmem_tiles_respect_budget():
    from repro.kernels.window_filter import (
        LANES,
        VMEM_TILE_BUDGET,
        tile_vmem_bytes,
        vmem_tiles,
    )

    for n, q, d, b in [(100_000, 64, 2, 4), (5000, 1024, 16, 4),
                       (128, 8, 3, 2)]:
        nt, qt = vmem_tiles(n, q, d, in_bytes=b)
        assert nt >= 8 and qt >= 8
        assert tile_vmem_bytes(nt, qt, b) <= VMEM_TILE_BUDGET \
            or (nt, qt) == (8, 8)
        # a (rows, d) tile is charged whole 128-lane rows whatever d is,
        # double-buffered, for both bound columns
        assert tile_vmem_bytes(nt, qt, b) >= 2 * 2 * nt * LANES * b
    # the budget stays inside the 16 MiB scoped-VMEM limit
    assert VMEM_TILE_BUDGET <= 16 * 1024 * 1024

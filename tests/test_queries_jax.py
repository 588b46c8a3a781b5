"""Device query engine: parity with the NumPy NodeTable engine + edge cases.

The parity contract (see ``core/queries_jax.py``): for float32-representable
inputs the compiled engine returns exactly the NumPy engine's result ids —
windows as sets (order unspecified), k-NN as ascending-distance sequences
(identical whenever distances are unique; under exact ties the id choice at
the k-th boundary may differ, so tie-heavy tests compare distances).  All
test data is generated float32-representable for that reason.
"""
import numpy as np
import pytest

from repro.core import (
    AMBI,
    PageStore,
    bulk_load,
    knn_oracle,
    knn_query,
    knn_query_batch,
    window_oracle,
    window_query,
    window_query_batch,
)
from repro.core import queries_jax as QJ
from repro.core.queries_jax import (
    DeviceTable,
    knn_query_batch_jax,
    window_query_batch_jax,
)
from repro.serve.engine import DeviceQueryServer

try:  # optional dev dependency (see requirements-dev.txt)
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _f32_points(n, d, seed, kind="uniform"):
    """Float32-representable coordinates (stored as float64)."""
    rng = np.random.default_rng(seed)
    if kind == "skew":
        pts = rng.random((n, d)) ** 3
    elif kind == "grid":  # heavy duplication, exact f32 arithmetic
        pts = rng.integers(0, 48, (n, d)) / np.float64(64.0)
    else:
        pts = rng.random((n, d))
    return pts.astype(np.float32).astype(np.float64)


def _build(pts, M=250):
    return bulk_load(pts, M, PageStore(M))


# the engine's jitted stages: a retrace shows as a new entry in one's cache
_STAGES = (QJ.frontier_leaf_hits, QJ._frontier_count, QJ._fused_pack_scan,
           QJ._fused_id_pack, QJ._pair_collect, QJ._knn_core,
           QJ._knn_core_fused, QJ._knn_pending, QJ._knn_merge_round)


def _compiles() -> dict:
    """Compiled variants per jitted stage, from the stages' own caches."""
    return {f.__name__: f._cache_size() for f in _STAGES}


def _knn_check(pts, q, got, want, k):
    """got/want are id arrays; require identical distance sequences and
    id agreement wherever the oracle distances are unique."""
    dg = np.sort(np.sum((pts[got] - q) ** 2, axis=1))
    dw = np.sort(np.sum((pts[want] - q) ** 2, axis=1))
    np.testing.assert_array_equal(dg, dw)
    if len(np.unique(dw)) == len(dw):  # no ties: ids must match exactly
        assert np.array_equal(np.sort(got), np.sort(want))


# --------------------------------------------------------------------------
# randomized parity: FMBI workloads (fixed seeds)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind,d,seed", [
    ("uniform", 2, 0), ("uniform", 3, 1), ("skew", 2, 2), ("skew", 4, 3),
])
def test_window_parity_fmbi(kind, d, seed):
    pts = _f32_points(6000, d, seed, kind)
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)
    rng = np.random.default_rng(seed + 100)
    centers = rng.random((24, d)).astype(np.float32).astype(np.float64)
    widths = rng.choice([0.01, 0.05, 0.2, 0.6], size=(24, 1))
    los = (centers - widths).astype(np.float32).astype(np.float64)
    his = (centers + widths).astype(np.float32).astype(np.float64)
    want, _ = window_query_batch(idx, los, his)
    got = window_query_batch_jax(dev, los, his)
    for i in range(24):
        assert np.array_equal(np.sort(got[i]), np.sort(want[i]))
        assert np.array_equal(
            np.sort(got[i]), window_oracle(pts, los[i], his[i])
        )


@pytest.mark.parametrize("k,seed", [(1, 0), (8, 1), (32, 2)])
def test_knn_parity_fmbi(k, seed):
    pts = _f32_points(6000, 2, seed)
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)
    rng = np.random.default_rng(seed + 200)
    qs = rng.random((24, 2)).astype(np.float32).astype(np.float64)
    want, _ = knn_query_batch(idx, qs, k)
    got = knn_query_batch_jax(dev, qs, k)
    for i in range(24):
        # continuous data, fixed seeds: ascending-distance ids identical
        assert np.array_equal(got[i], want[i])
        assert np.array_equal(got[i], knn_oracle(pts, qs[i], k))


# --------------------------------------------------------------------------
# randomized parity: AMBI-snapshot workloads
# --------------------------------------------------------------------------
def _refined_ambi(pts, M=250):
    ambi = AMBI(pts, M)
    ambi.window(np.zeros(pts.shape[1]), np.ones(pts.shape[1]))
    assert ambi.is_fully_refined()
    return ambi


def test_parity_ambi_snapshot(tmp_path):
    """AMBI refines on demand (grafted rows are not level-contiguous);
    its snapshot must lay out and answer identically."""
    pts = _f32_points(8000, 2, 7, "skew")
    ambi = _refined_ambi(pts)
    snap = tmp_path / "ambi.npz"
    ambi.index.save(snap)

    srv = DeviceQueryServer.from_snapshot(snap)
    rng = np.random.default_rng(8)
    centers = rng.random((16, 2)).astype(np.float32).astype(np.float64)
    los = (centers - 0.05).astype(np.float32).astype(np.float64)
    his = (centers + 0.05).astype(np.float32).astype(np.float64)
    want, _ = window_query_batch(ambi.index, los, his)
    got = srv.window(los, his)
    for i in range(16):
        assert np.array_equal(np.sort(got[i]), np.sort(want[i]))
    qs = rng.random((16, 2)).astype(np.float32).astype(np.float64)
    wantk, _ = knn_query_batch(ambi.index, qs, 8)
    gotk = srv.knn(qs, 8)
    for i in range(16):
        assert np.array_equal(gotk[i], wantk[i])


def test_unrefined_table_is_rejected():
    pts = _f32_points(4000, 2, 3)
    ambi = AMBI(pts, 250)  # nothing refined yet
    with pytest.raises(ValueError, match="fully refined"):
        DeviceTable.from_table(ambi.table, pts)


# --------------------------------------------------------------------------
# Pallas kernel path (interpret mode on CPU)
# --------------------------------------------------------------------------
def test_kernel_path_matches_jnp_path():
    pts = _f32_points(3000, 2, 11)
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)
    rng = np.random.default_rng(12)
    centers = rng.random((8, 2)).astype(np.float32).astype(np.float64)
    los, his = centers - 0.08, centers + 0.08
    qs = rng.random((8, 2)).astype(np.float32).astype(np.float64)
    w_jnp = window_query_batch_jax(dev, los, his, use_kernel=False)
    w_ker = window_query_batch_jax(dev, los, his, use_kernel=True)
    k_jnp = knn_query_batch_jax(dev, qs, 8, use_kernel=False)
    k_ker = knn_query_batch_jax(dev, qs, 8, use_kernel=True)
    for i in range(8):
        assert np.array_equal(np.sort(w_jnp[i]), np.sort(w_ker[i]))
        assert np.array_equal(k_jnp[i], k_ker[i])


# --------------------------------------------------------------------------
# edge cases: k >= n, duplicates, zero-volume windows, single-query batches
# (parity against the oracles and the single-query engines)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("k", [600, 1000])
def test_knn_k_geq_n(k):
    pts = _f32_points(600, 2, 5)
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)
    qs = np.random.default_rng(6).random((4, 2)).astype(
        np.float32).astype(np.float64)
    want, _ = knn_query_batch(idx, qs, k)
    got = knn_query_batch_jax(dev, qs, k)
    for i in range(4):
        assert len(got[i]) == len(pts)  # every point, ascending distance
        _knn_check(pts, qs[i], got[i], want[i], k)
        _knn_check(pts, qs[i], got[i], knn_oracle(pts, qs[i], k), k)
        single, _ = knn_query(idx, qs[i], k)
        _knn_check(pts, qs[i], got[i], single, k)


def test_duplicate_coordinates():
    """Grid-quantized data: many exactly coincident points and exact-tie
    distances.  Distances must agree everywhere; ids wherever unique."""
    pts = _f32_points(5000, 2, 9, "grid")
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)
    rng = np.random.default_rng(10)
    qs = (rng.integers(0, 48, (8, 2)) / 64.0).astype(np.float64)
    want, _ = knn_query_batch(idx, qs, 16)
    got = knn_query_batch_jax(dev, qs, 16)
    for i in range(8):
        _knn_check(pts, qs[i], got[i], want[i], 16)
        single, _ = knn_query(idx, qs[i], 16)
        _knn_check(pts, qs[i], got[i], single, 16)
    # windows have no tie ambiguity even on duplicated coordinates
    los = qs - 3 / 64.0
    his = qs + 3 / 64.0
    wantw, _ = window_query_batch(idx, los, his)
    gotw = window_query_batch_jax(dev, los, his)
    for i in range(8):
        assert np.array_equal(np.sort(gotw[i]), np.sort(wantw[i]))
        assert np.array_equal(
            np.sort(gotw[i]), window_oracle(pts, los[i], his[i])
        )


def test_zero_volume_windows():
    """lo == hi windows: exactly the points at that coordinate."""
    pts = _f32_points(4000, 2, 13, "grid")
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)
    los = np.concatenate([pts[:3], [[0.9999, 0.9999]]])  # 3 hits + 1 miss
    his = los.copy()
    want, _ = window_query_batch(idx, los, his)
    got = window_query_batch_jax(dev, los, his)
    for i in range(4):
        assert np.array_equal(np.sort(got[i]), np.sort(want[i]))
        assert np.array_equal(
            np.sort(got[i]), window_oracle(pts, los[i], his[i])
        )
        single, _ = window_query(idx, los[i], his[i])
        assert np.array_equal(np.sort(got[i]), np.sort(single))
    assert len(got[0]) >= 1 and len(got[3]) == 0


def test_single_query_batches():
    pts = _f32_points(3000, 3, 14)
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)
    q = np.asarray([[0.5, 0.5, 0.5]])
    lo, hi = q - 0.1, q + 0.1
    got = window_query_batch_jax(dev, lo, hi)
    assert len(got) == 1
    single, _ = window_query(idx, lo[0], hi[0])
    assert np.array_equal(np.sort(got[0]), np.sort(single))
    wb, _ = window_query_batch(idx, lo, hi)
    assert np.array_equal(np.sort(wb[0]), np.sort(got[0]))
    gotk = knn_query_batch_jax(dev, q, 5)
    assert len(gotk) == 1
    singlek, _ = knn_query(idx, q[0], 5)
    assert np.array_equal(gotk[0], singlek)
    kb, _ = knn_query_batch(idx, q, 5)
    assert np.array_equal(kb[0], gotk[0])


def test_empty_result_windows():
    pts = _f32_points(3000, 2, 15)
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)
    los = np.full((3, 2), 2.0)  # entirely outside the data domain
    his = los + 0.1
    got = window_query_batch_jax(dev, los, his)
    assert all(len(g) == 0 for g in got)


# --------------------------------------------------------------------------
# hypothesis: randomized workloads (grid coordinates keep f32 exact)
# --------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    _IDX_CACHE = {}

    def _cached(seed):
        if seed not in _IDX_CACHE:
            pts = _f32_points(4000, 2, seed, "grid")
            idx = _build(pts)
            _IDX_CACHE[seed] = (pts, idx, DeviceTable.from_index(idx))
        return _IDX_CACHE[seed]

    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2),
        qseed=st.integers(0, 10_000),
        w=st.integers(1, 12),
        k=st.integers(1, 24),
    )
    def test_hypothesis_parity(seed, qseed, w, k):
        pts, idx, dev = _cached(seed)
        rng = np.random.default_rng(qseed)
        centers = rng.integers(0, 48, (6, 2)) / 64.0
        los = centers - w / 64.0
        his = centers + w / 64.0
        want, _ = window_query_batch(idx, los, his)
        got = window_query_batch_jax(dev, los, his)
        for i in range(6):
            assert np.array_equal(np.sort(got[i]), np.sort(want[i]))
        wantk, _ = knn_query_batch(idx, centers, k)
        gotk = knn_query_batch_jax(dev, centers, k)
        for i in range(6):
            _knn_check(pts, centers[i], gotk[i], wantk[i], k)


# --------------------------------------------------------------------------
# serving: microbatching + compile-variant bounding
# --------------------------------------------------------------------------
def test_device_server_microbatching():
    pts = _f32_points(6000, 2, 21)
    idx = _build(pts)
    srv = DeviceQueryServer.from_index(idx, microbatch=32)
    rng = np.random.default_rng(22)
    centers = rng.random((100, 2)).astype(np.float32).astype(np.float64)
    los, his = centers - 0.04, centers + 0.04
    got = srv.window(los, his)
    assert len(got) == 100
    assert srv.stats.microbatches == 4  # ceil(100 / 32)
    want, _ = window_query_batch(idx, los, his)
    for i in range(100):
        assert np.array_equal(np.sort(got[i]), np.sort(want[i]))
    gotk = srv.knn(centers[:50], 8)
    wantk, _ = knn_query_batch(idx, centers[:50], 8)
    for i in range(50):
        assert np.array_equal(gotk[i], wantk[i])
    assert srv.stats.queries == 150


def test_compile_variants_bounded_across_workload_drift():
    """Growing window widths / batch sizes must not grow compilations
    without bound: a repeated sweep adds zero retraces."""
    pts = _f32_points(6000, 2, 31)
    idx = _build(pts)
    dev = DeviceTable.from_index(idx)

    def sweep():
        rng = np.random.default_rng(32)  # same workload every sweep
        for q, w in [(3, 0.01), (5, 0.03), (7, 0.08), (8, 0.15), (6, 0.3)]:
            centers = rng.random((q, 2)).astype(np.float32)
            window_query_batch_jax(dev, centers - w, centers + w)
            knn_query_batch_jax(dev, centers, 8)

    sweep()  # warm every bucket the workload can reach
    before = _compiles()
    sweep()
    sweep()
    assert _compiles() == before


# --------------------------------------------------------------------------
# PR-7 fused path: parity, env pin, and bounded recompiles
# --------------------------------------------------------------------------
@pytest.mark.parametrize("compressed", [False, True])
def test_fused_engine_matches_unfused(compressed):
    """The fused (on-device packed) pipeline is id-identical to the
    first-generation path on the same export — window sets and k-NN
    sequences — including a starved k-NN budget that must escalate."""
    pts = _f32_points(5000, 3, 71, kind="skew")
    idx = _build(pts)
    dev = DeviceTable.from_index(idx, compressed=compressed)
    rng = np.random.default_rng(72)
    ctr = rng.random((19, 3))  # odd batch: pow2 padding rows in play
    los, his = ctr - 0.06, ctr + 0.06
    w0 = window_query_batch_jax(dev, los, his, fused=False)
    w1 = window_query_batch_jax(dev, los, his, fused=True)
    for a, b in zip(w0, w1):
        assert set(np.asarray(a).tolist()) == set(np.asarray(b).tolist())
    k0 = knn_query_batch_jax(dev, ctr, 10, fused=False,
                             n_candidate_leaves=1)
    k1 = knn_query_batch_jax(dev, ctr, 10, fused=True,
                             n_candidate_leaves=1)
    for a, b in zip(k0, k1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_default_env_pin(monkeypatch):
    monkeypatch.delenv("REPRO_FUSED", raising=False)
    assert QJ._fused_default() is True
    monkeypatch.setenv("REPRO_FUSED", "0")
    assert QJ._fused_default() is False
    monkeypatch.setenv("REPRO_FUSED", "1")
    assert QJ._fused_default() is True


def test_fused_recompile_bounded():
    """The fused path's pow2 bucketing keeps compiled variants bounded:
    a repeated mixed sweep (both layouts, drifting widths and batch
    sizes, escalating k-NN budgets) adds zero retraces after warmup —
    including the new pair-pack / id-pack / pending-selection jits."""
    pts = _f32_points(6000, 2, 73)
    idx = _build(pts)
    devs = [DeviceTable.from_index(idx, compressed=c)
            for c in (False, True)]

    def sweep():
        rng = np.random.default_rng(74)  # same workload every sweep
        for dev in devs:
            for q, w in [(3, 0.01), (5, 0.05), (8, 0.2), (6, 0.4)]:
                centers = rng.random((q, 2)).astype(np.float32)
                window_query_batch_jax(dev, centers - w, centers + w,
                                       fused=True)
                knn_query_batch_jax(dev, centers, 8, fused=True,
                                    n_candidate_leaves=1)

    sweep()  # warm every bucket the workload can reach
    before = _compiles()
    sweep()
    sweep()
    assert _compiles() == before


def test_fused_partial_export_cold_mask():
    """return_cold on the fused path surfaces the same cold-hit rows as
    the first-generation path on a partial export."""
    pts = _f32_points(4000, 2, 75)
    ambi = AMBI(pts, 250)
    c = np.asarray([0.5, 0.5])
    ambi.window(c - 0.05, c + 0.05)  # refine one hotspot only
    dev = DeviceTable.from_table(ambi.table, ambi.points, partial=True)
    rng = np.random.default_rng(76)
    ctr = rng.random((9, 2))
    los, his = ctr - 0.08, ctr + 0.08
    r0, cold0 = window_query_batch_jax(dev, los, his, fused=False,
                                       return_cold=True)
    r1, cold1 = window_query_batch_jax(dev, los, his, fused=True,
                                       return_cold=True)
    np.testing.assert_array_equal(np.asarray(cold0), np.asarray(cold1))
    for a, b in zip(r0, r1):
        assert set(np.asarray(a).tolist()) == set(np.asarray(b).tolist())

"""Sharded device engine: parity with the single-table engines + edge cases.

The parity harness (``tests/engines.py``) runs the NumPy NodeTable engine,
the single DeviceTable engine, and the m-shard engine for m in {1, 2, 4}
over the same FMBI and grafted-AMBI tables and asserts id-identical
results — the same pinning discipline ``test_flat_queries.py`` applied to
PR 2 and ``test_queries_jax.py`` to PR 3.  Edge cases: m=1, shards with
zero qualifying leaves, k >= points-per-shard, queries straddling shard
boundaries, duplicate coordinates.  The shard_map collective rounds run in
a subprocess with forced virtual devices (CI also runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and Pallas
interpret mode).
"""
import subprocess
import sys

import numpy as np
import pytest

from engines import (
    assert_knn_parity,
    assert_window_parity,
    build_fmbi,
    build_grafted_ambi,
    engine_suite,
    f32_points,
)
from repro.core import distributed_jax as DJ
from repro.core.distributed import parallel_bulk_load
from repro.core.distributed_jax import (
    ShardedDeviceTable,
    knn_query_batch_sharded,
    window_query_batch_sharded,
)
from repro.core.geometry import boxes_intersect_windows
from repro.core.queries_jax import knn_query_batch_jax, window_query_batch_jax
from repro.serve.engine import DeviceQueryServer

try:  # optional dev dependency (see requirements-dev.txt)
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _windows(rng, d, n, width):
    centers = rng.random((n, d)).astype(np.float32).astype(np.float64)
    return centers - width, centers + width, centers


# --------------------------------------------------------------------------
# parity harness: all engines over the same tables (acceptance criterion)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kind,d,seed", [
    ("uniform", 2, 0), ("uniform", 3, 1), ("skew", 2, 2),
])
def test_parity_fmbi(kind, d, seed):
    pts = f32_points(6000, d, seed, kind)
    engines = engine_suite(build_fmbi(pts))
    rng = np.random.default_rng(seed + 50)
    los, his, centers = _windows(rng, d, 16, 0.06)
    assert_window_parity(engines, los, his)
    assert_knn_parity(engines, pts, centers, 10)


def test_parity_grafted_ambi():
    pts = f32_points(8000, 2, 7, "skew")
    engines = engine_suite(build_grafted_ambi(pts))
    rng = np.random.default_rng(8)
    los, his, centers = _windows(rng, 2, 16, 0.05)
    assert_window_parity(engines, los, his)
    assert_knn_parity(engines, pts, centers, 8)


def test_duplicate_coordinates():
    """Grid-quantized data: coincident points and exact-tie distances.
    Distances must agree everywhere, ids wherever unique."""
    pts = f32_points(5000, 2, 9, "grid")
    engines = engine_suite(build_fmbi(pts))
    rng = np.random.default_rng(10)
    qs = (rng.integers(0, 48, (8, 2)) / 64.0).astype(np.float64)
    assert_window_parity(engines, qs - 3 / 64.0, qs + 3 / 64.0)
    assert_knn_parity(engines, pts, qs, 16, ids_exact=False)


# --------------------------------------------------------------------------
# edge cases
# --------------------------------------------------------------------------
def test_m1_identical_to_single_table_engine():
    pts = f32_points(4000, 2, 3)
    idx = build_fmbi(pts)
    sdev = ShardedDeviceTable.from_index(idx, 1)
    assert sdev.m == 1
    from repro.core.queries_jax import DeviceTable

    dev = DeviceTable.from_index(idx)
    rng = np.random.default_rng(4)
    los, his, centers = _windows(rng, 2, 8, 0.08)
    for a, b in zip(window_query_batch_sharded(sdev, los, his),
                    window_query_batch_jax(dev, los, his)):
        assert np.array_equal(np.sort(a), np.sort(b))
    for a, b in zip(knn_query_batch_sharded(sdev, centers, 7),
                    knn_query_batch_jax(dev, centers, 7)):
        assert np.array_equal(a, b)


def test_window_fans_out_only_to_qualified_shards(monkeypatch):
    """A shard whose subspace MBB misses every query box must receive no
    dispatch at all (zero qualifying leaves => zero work)."""
    pts = f32_points(6000, 2, 11)
    sdev = ShardedDeviceTable.from_index(build_fmbi(pts), 4)
    # narrow boxes just inside shard 0's subspace corner
    lo0 = sdev.shard_lo[0].astype(np.float64)
    los = np.tile(lo0, (3, 1))
    his = los + 1e-4
    hit = boxes_intersect_windows(sdev.shard_lo, sdev.shard_hi,
                                  los.astype(np.float32),
                                  his.astype(np.float32))
    assert not hit.all(), "boxes must miss at least one shard"
    dispatched = []
    real = DJ.window_query_batch_jax

    def spy(dev, *a, **kw):
        dispatched.append(id(dev))
        return real(dev, *a, **kw)

    monkeypatch.setattr(DJ, "window_query_batch_jax", spy)
    got = window_query_batch_sharded(sdev, los, his)
    probed = {id(sdev.shards[s]) for s in range(4) if hit[:, s].any()}
    assert set(dispatched) == probed
    for i in range(3):
        oracle = np.flatnonzero(
            np.all((pts >= los[i]) & (pts <= his[i]), axis=1)
        )
        assert np.array_equal(np.sort(got[i]), oracle)


def test_windows_entirely_outside_all_shards():
    pts = f32_points(3000, 2, 15)
    sdev = ShardedDeviceTable.from_index(build_fmbi(pts), 4)
    los = np.full((3, 2), 2.0)
    got = window_query_batch_sharded(sdev, los, los + 0.1)
    assert all(len(g) == 0 for g in got)


def test_k_geq_points_per_shard():
    """k larger than any single shard forces the +inf pruning radius and
    full escalation; results must still be the exact global top-k."""
    pts = f32_points(2000, 2, 5)
    idx = build_fmbi(pts)
    engines = engine_suite(idx, ms=(2, 4))
    qs = np.random.default_rng(6).random((4, 2)).astype(
        np.float32).astype(np.float64)
    for k in (600, 1200, 2500):  # > n/4, > n/2, > n
        ref = assert_knn_parity(engines, pts, qs, k, ids_exact=False)
        want_len = min(k, len(pts))
        assert all(len(r) == want_len for r in ref)


def test_queries_straddling_shard_boundaries():
    """Wide windows and centroid k-NN hit several shards at once."""
    pts = f32_points(6000, 2, 12)
    engines = engine_suite(build_fmbi(pts))
    center = np.float64(np.float32(0.5))
    los = np.array([[center - 0.4, center - 0.4],
                    [0.0, center - 0.01],
                    [center - 0.01, 0.0]])
    his = np.array([[center + 0.4, center + 0.4],
                    [1.0, center + 0.01],
                    [center + 0.01, 1.0]])
    assert_window_parity(engines, los, his)
    qs = np.array([[center, center], [center, 0.1], [0.9, center]])
    assert_knn_parity(engines, pts, qs, 24)
    # the wide window really does straddle: >1 shard qualifies
    for eng in engines:
        if getattr(eng, "sdev", None) is not None and eng.sdev.m > 1:
            hit = boxes_intersect_windows(
                eng.sdev.shard_lo, eng.sdev.shard_hi,
                los.astype(np.float32), his.astype(np.float32))
            assert hit[0].sum() > 1


def test_sharded_kernel_path_matches_jnp_path():
    """The Pallas leaf kernels behind each shard (interpret mode on CPU CI)
    return the jnp path's results through the distributed rounds too."""
    pts = f32_points(3000, 2, 11)
    sdev = ShardedDeviceTable.from_index(build_fmbi(pts), 2)
    rng = np.random.default_rng(12)
    los, his, centers = _windows(rng, 2, 6, 0.08)
    w_jnp = window_query_batch_sharded(sdev, los, his, use_kernel=False)
    w_ker = window_query_batch_sharded(sdev, los, his, use_kernel=True)
    k_jnp = knn_query_batch_sharded(sdev, centers, 8, use_kernel=False)
    k_ker = knn_query_batch_sharded(sdev, centers, 8, use_kernel=True)
    for i in range(6):
        assert np.array_equal(np.sort(w_jnp[i]), np.sort(w_ker[i]))
        assert np.array_equal(k_jnp[i], k_ker[i])


# --------------------------------------------------------------------------
# hypothesis: randomized workloads (grid coordinates keep f32 exact)
# --------------------------------------------------------------------------
if HAVE_HYPOTHESIS:
    _SUITE_CACHE = {}

    def _cached(seed):
        if seed not in _SUITE_CACHE:
            pts = f32_points(4000, 2, seed, "grid")
            _SUITE_CACHE[seed] = (pts, engine_suite(build_fmbi(pts)))
        return _SUITE_CACHE[seed]

    @settings(max_examples=15, deadline=None)
    @given(
        seed=st.integers(0, 1),
        qseed=st.integers(0, 10_000),
        w=st.integers(1, 12),
        k=st.integers(1, 24),
    )
    def test_hypothesis_parity(seed, qseed, w, k):
        pts, engines = _cached(seed)
        rng = np.random.default_rng(qseed)
        centers = rng.integers(0, 48, (5, 2)) / 64.0
        assert_window_parity(engines, centers - w / 64.0, centers + w / 64.0)
        assert_knn_parity(engines, pts, centers, k, ids_exact=False)


# --------------------------------------------------------------------------
# one representation: host m-server build and TPU build feed one engine
# --------------------------------------------------------------------------
def test_from_parallel_build_serves_globally():
    """The Figure-11 m-server simulation ships straight into the sharded
    device engine (per-server subtrees become the shards verbatim)."""
    pts = f32_points(20_000, 2, 31)
    build = parallel_bulk_load(pts, m=4, buffer_pages=600)
    sdev = ShardedDeviceTable.from_parallel_build(build, pts)
    assert sdev.m == 4
    assert sdev.n_points == len(pts)
    rng = np.random.default_rng(3)
    los, his, centers = _windows(rng, 2, 8, 0.04)
    got = window_query_batch_sharded(sdev, los, his)
    for i in range(8):
        oracle = np.flatnonzero(
            np.all((pts >= los[i]) & (pts <= his[i]), axis=1)
        )
        assert np.array_equal(np.sort(got[i]), oracle)
    gotk = knn_query_batch_sharded(sdev, centers, 12)
    for i in range(8):
        d2 = np.sum((pts - centers[i]) ** 2, axis=1)
        want = np.sort(d2)[:12]
        np.testing.assert_array_equal(
            np.sort(d2[gotk[i]]), want
        )


# --------------------------------------------------------------------------
# serving: DeviceQueryServer shards= mode
# --------------------------------------------------------------------------
def test_device_server_sharded_mode():
    pts = f32_points(6000, 2, 21)
    idx = build_fmbi(pts)
    srv1 = DeviceQueryServer.from_index(idx, microbatch=32)
    srv4 = DeviceQueryServer.from_index(idx, microbatch=32, shards=4)
    assert srv4.stats.shards == 4 and srv1.stats.shards == 1
    rng = np.random.default_rng(22)
    centers = rng.random((80, 2)).astype(np.float32).astype(np.float64)
    los, his = centers - 0.04, centers + 0.04
    w1, w4 = srv1.window(los, his), srv4.window(los, his)
    for a, b in zip(w1, w4):
        assert np.array_equal(np.sort(a), np.sort(b))
    k1, k4 = srv1.knn(centers[:40], 8), srv4.knn(centers[:40], 8)
    for a, b in zip(k1, k4):
        assert np.array_equal(a, b)
    assert srv4.stats.microbatches == 3 + 2  # ceil(80/32) + ceil(40/32)
    assert srv4.stats.queries == 120


# --------------------------------------------------------------------------
# shard_map collective rounds (forced virtual devices, subprocess so the
# device count never leaks into this process)
# --------------------------------------------------------------------------
SHARD_MAP_SCRIPT = r"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
import sys; sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
if len(jax.devices()) < 4:
    print(f"SMAP-SKIP: only {len(jax.devices())} devices"); sys.exit(0)
from repro.core import PageStore, bulk_load, distributed
from repro.core.distributed_jax import (
    ShardedDeviceTable, knn_batch_shard_map, knn_query_batch_sharded,
    window_count_batch_shard_map,
)
from repro.core.queries_jax import DeviceTable, knn_query_batch_jax
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
pts = rng.random((8192, 2)).astype(np.float32).astype(np.float64)
idx = bulk_load(pts, 250, PageStore(250))
sdev = ShardedDeviceTable.from_index(idx, 4)
assert sdev.m == 4
st = sdev.stacked()
qs = rng.random((8, 2)).astype(np.float32)
# collective two-round k-NN == single-table engine ids
d2, ids = knn_batch_shard_map(st, qs, 8, mesh)
want = knn_query_batch_jax(DeviceTable.from_index(idx), qs, 8)
for i in range(8):
    assert np.array_equal(ids[i], want[i]), (i, ids[i], want[i])
# collective window counts == oracle
los, his = qs - 0.07, qs + 0.07
cnt = window_count_batch_shard_map(st, los, his, mesh)
lo64 = los.astype(np.float64); hi64 = his.astype(np.float64)
oracle = np.array([np.sum(np.all((pts >= l) & (pts <= h), 1))
                   for l, h in zip(lo64, hi64)])
np.testing.assert_array_equal(cnt, oracle)
# shard_build carries global row ids and lands on the NodeTable path
pts32 = pts.astype(np.float32)
out = distributed.shard_build(jnp.asarray(pts32), mesh, levels_local=4)
ri = np.asarray(out[1]).ravel()
valid = ri[ri >= 0]
assert len(np.unique(valid)) == len(valid), "duplicate row ids"
assert valid.min() >= 0 and valid.max() < len(pts)
tables = distributed.shard_build_tables(out, 4)
live = 0
for t in tables:
    t.check_invariants()
    live += int(t.leaf_count[t.leaf_rows()].sum())
assert live == int(np.asarray(out[6]).sum())
sdev2 = ShardedDeviceTable.from_tables(tables, pts)
got = knn_query_batch_sharded(sdev2, qs, 8)
kept = np.isin(np.arange(len(pts)), valid)
for i, q in enumerate(qs):
    d2o = np.sum((pts[kept] - q.astype(np.float64)) ** 2, 1)
    want_d = np.sort(d2o)[:8]
    got_d = np.sort(np.sum((pts[got[i]] - q.astype(np.float64)) ** 2, 1))
    np.testing.assert_allclose(got_d, want_d, rtol=1e-6)
print("SMAP-OK")
"""


def test_shard_map_collective_rounds_4dev():
    res = subprocess.run(
        [sys.executable, "-c", SHARD_MAP_SCRIPT], capture_output=True,
        text=True,
        cwd=str(__import__("pathlib").Path(__file__).resolve().parents[1]),
        timeout=600,
    )
    if "SMAP-SKIP" in res.stdout:
        pytest.skip("could not provision 4 virtual devices: "
                    + res.stdout.strip())
    assert "SMAP-OK" in res.stdout, res.stdout + res.stderr

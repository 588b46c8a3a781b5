"""Adaptive device serving: AMBI behind DeviceQueryServer.

The acceptance criterion: ``DeviceQueryServer(adaptive=True)`` boots from
the single-unrefined-root AMBI state and serves a pinned hotspot stream
with window/k-NN results id-identical to the host AMBI engine, while the
upload counters prove each graft re-uploads only its delta — no full
``DeviceTable`` re-export after the initial boot.

Also here: the partial device layout's cold mask, ``apply_delta`` vs a
fresh full export, targeted ``ShardedDeviceTable.refresh``, the
``NodeTable.compact`` vacuum under graft churn (hypothesis + fixed
seeds), the DeviceTable pytree round-trip regression, the
RetrievalServer LRU-policy regression, and the explicit query-context
refiner contract.
"""
import jax
import numpy as np
import pytest

from repro.core import AMBI, PageStore, bulk_load, knn_oracle, window_oracle
from repro.core import queries_jax as QJ
from repro.core.geometry import boxes_intersect_windows
from repro.core.nodetable import LEAF_TILE, SLOT_TILE, round_up
from repro.core.queries import knn_query_batch, window_query_batch
from repro.core.queries_jax import (
    DeviceTable,
    knn_query_batch_jax,
    window_query_batch_jax,
)
from repro.serve.engine import DeviceQueryServer, RetrievalServer

try:  # optional dev dependency (see requirements-dev.txt)
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _f32_points(n, d, seed, kind="uniform"):
    rng = np.random.default_rng(seed)
    pts = rng.random((n, d)) ** (3 if kind == "skew" else 1)
    return pts.astype(np.float32).astype(np.float64)


def _hotspot_stream(d, steps, per_step, seed):
    """Pinned stream alternating two hotspots (the workload AMBI's partial
    index exists for: most of the space is never touched)."""
    rng = np.random.default_rng(seed)
    centers = [np.full(d, 0.3), np.full(d, 0.7)]
    out = []
    for s in range(steps):
        c = centers[s % 2] + rng.random((per_step, d)) * 0.08
        out.append(c.astype(np.float32).astype(np.float64))
    return out


# --------------------------------------------------------------------------
# acceptance: unrefined-root boot, host parity, delta-only uploads
# --------------------------------------------------------------------------
def test_adaptive_server_hotspot_stream_parity_and_delta_uploads():
    pts = _f32_points(100_000, 2, 0)
    M = 120  # 294 data pages >> M: the root is dense, refinement is real
    host = AMBI(pts, M)           # the reference engine, driven identically
    ambi = AMBI(pts, M)
    QJ.reset_upload_stats()  # the module-level default sink, for the
    # no-leak assertion at the end — the server's own counters are fresh
    srv = DeviceQueryServer.from_ambi(ambi, microbatch=8)
    assert srv.upload_stats["full_exports"] == 1  # the boot
    assert srv.dev.n_leaves == 0 and srv.dev.n_cold == 1

    for step, batch in enumerate(_hotspot_stream(2, 10, 8, 1)):
        los, his = batch - 0.02, batch + 0.02
        got_w = srv.window(los, his)
        got_k = srv.knn(batch, 8)
        for i in range(len(batch)):
            want_w, _ = host.window(los[i], his[i])
            assert np.array_equal(np.sort(got_w[i]), np.sort(want_w)), (
                step, i)
            want_k, _ = host.knn(batch[i], 8)
            assert np.array_equal(got_k[i], want_k), (step, i)

    # the workload is focused: the index stays partial, serving went hot
    assert not ambi.is_fully_refined()
    assert srv.stats.cold_queries > 0 and srv.stats.hot_queries > 0
    assert srv.stats.grafts > 0 and srv.stats.delta_refreshes > 0
    # upload accounting: one boot export, every graft shipped only its
    # delta — each leaf block crossed the host/device boundary exactly once
    assert srv.upload_stats["full_exports"] == 1
    assert srv.upload_stats["delta_refreshes"] == srv.stats.delta_refreshes
    assert srv.upload_stats["uploaded_leaf_blocks"] == srv.dev.n_leaves
    assert srv.upload_stats["uploaded_points"] == srv.dev.n_points
    # instance-scoped counters: this server's uploads never leaked into
    # the module-level default sink
    assert QJ.UPLOAD_STATS["full_exports"] == 0
    ambi.table.check_invariants(len(pts))

    # steady state: replaying the pinned hotspots is all-device, no I/O
    cold_before = srv.stats.cold_queries
    io_before = ambi.store.stats.total
    for batch in _hotspot_stream(2, 4, 8, 1)[:2]:
        srv.window(batch - 0.02, batch + 0.02)
        srv.knn(batch, 8)
    assert srv.stats.cold_queries == cold_before
    assert ambi.store.stats.total == io_before


def test_adaptive_server_converges_to_refined_and_stays_device_only():
    pts = _f32_points(40_000, 2, 3)
    ambi = AMBI(pts, 80)
    srv = DeviceQueryServer.from_ambi(ambi, microbatch=4)
    res = srv.window(np.zeros((1, 2)), np.ones((1, 2)))
    assert len(res[0]) == len(pts)
    assert ambi.is_fully_refined()
    assert srv.dev.n_cold == 0
    idx = bulk_load(pts, 250, PageStore(250))
    qs = _f32_points(8, 2, 4)
    want, _ = knn_query_batch(idx, qs, 16)
    got = srv.knn(qs, 16)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    assert srv.stats.cold_queries == 1  # only the covering window


# --------------------------------------------------------------------------
# partial layout: the frontier's cold mask
# --------------------------------------------------------------------------
def _partially_refined(pts, M=120, seed=5):
    ambi = AMBI(pts, M)
    rng = np.random.default_rng(seed)
    for _ in range(3):
        c = rng.random(2) * 0.2 + 0.4
        ambi.window(c - 0.03, c + 0.03)
    assert not ambi.is_fully_refined()
    return ambi


def test_partial_layout_cold_mask_matches_host_geometry():
    pts = _f32_points(60_000, 2, 5)
    ambi = _partially_refined(pts)
    t = ambi.table
    dev = DeviceTable.from_table(t, pts, partial=True)
    assert dev.n_cold == int(t.unrefined.sum()) > 0
    rng = np.random.default_rng(6)
    c = rng.random((32, 2)).astype(np.float32).astype(np.float64)
    los, his = c - 0.04, c + 0.04
    res, cold = window_query_batch_jax(dev, los, his, return_cold=True)
    assert cold.shape == (32, dev.n_cold)
    # reaching an unrefined row == intersecting its MBB (downward-closed
    # hit sets), so the mask equals the host-side box test
    unref = np.flatnonzero(t.unrefined)
    assert np.array_equal(dev.cold_rows, unref)  # cold columns = row order
    want = boxes_intersect_windows(
        t.mbb_lo[unref],
        t.mbb_hi[unref],
        los.astype(np.float32).astype(np.float64),
        his.astype(np.float32).astype(np.float64),
    )
    assert np.array_equal(cold, want)
    # hot-query device results equal the refined part of the oracle
    cold_rows_pts = set()
    for r in unref:
        cold_rows_pts.update(t.point_rows(r).tolist())
    for i in np.flatnonzero(~cold.any(axis=1)):
        want_ids = window_oracle(pts, los[i], his[i])
        assert not (set(want_ids.tolist()) & cold_rows_pts)
        assert np.array_equal(np.sort(res[i]), np.sort(want_ids))


def test_device_layout_still_rejects_unrefined_without_partial():
    pts = _f32_points(60_000, 2, 5)
    ambi = _partially_refined(pts)
    with pytest.raises(ValueError, match="partial"):
        ambi.table.device_layout(pts)


# --------------------------------------------------------------------------
# apply_delta: incremental refresh == fresh full export
# --------------------------------------------------------------------------
def test_apply_delta_matches_full_export_and_uploads_only_new_leaves():
    pts = _f32_points(60_000, 2, 7)
    ambi = AMBI(pts, 120)
    dev = DeviceTable.from_table(ambi.table, pts, partial=True)
    rng = np.random.default_rng(8)
    for step in range(4):
        c = rng.random(2) * 0.6 + 0.2
        ambi.window(c - 0.04, c + 0.04)  # grafts
        QJ.reset_upload_stats()
        n_before = dev.n_leaves
        dev = dev.apply_delta(ambi.table, pts)
        delta_blocks = QJ.UPLOAD_STATS["uploaded_leaf_blocks"]
        fresh = DeviceTable.from_table(ambi.table, pts, partial=True)
        assert QJ.UPLOAD_STATS["delta_refreshes"] == 1
        # the delta shipped exactly the new leaves — strictly fewer than a
        # full export once there is a retained prefix
        assert delta_blocks == fresh.n_leaves - n_before
        if step > 0:
            assert delta_blocks < fresh.n_leaves, step
        assert dev.n_leaves == fresh.n_leaves
        assert dev.n_cold == fresh.n_cold
        assert dev.n_points == fresh.n_points
        # same leaf content (slot order may differ) ...
        def key(d):
            ids = np.asarray(d.leaf_ids)
            return sorted(tuple(sorted(row[row >= 0])) for row in ids)
        assert key(dev) == key(fresh)
        # ... and identical query behaviour
        qs = (rng.random((16, 2)) * 0.8 + 0.1)
        qs = qs.astype(np.float32).astype(np.float64)
        rw, cw = window_query_batch_jax(dev, qs - 0.03, qs + 0.03,
                                        return_cold=True)
        fw, fcold = window_query_batch_jax(fresh, qs - 0.03, qs + 0.03,
                                           return_cold=True)
        for a, b in zip(rw, fw):
            assert np.array_equal(np.sort(a), np.sort(b))
        assert np.array_equal(cw.any(axis=1), fcold.any(axis=1))
        rk = knn_query_batch_jax(dev, qs, 8)
        fk = knn_query_batch_jax(fresh, qs, 8)
        for a, b in zip(rk, fk):
            assert np.array_equal(a, b)


def test_exports_pad_the_tables_to_whole_tiles():
    """A full export and each delta refresh hold each leaf's points
    dimension-major in whole TPU tiles: slots in whole lane rows, the
    point table's leaves in whole sublane tiles, padding masked out."""
    pts = _f32_points(60_000, 2, 7)
    ambi = AMBI(pts, 120)
    tables = [DeviceTable.from_table(ambi.table, pts, partial=True)]
    rng = np.random.default_rng(3)
    for _ in range(3):
        c = rng.random(2) * 0.6 + 0.2
        ambi.window(c - 0.04, c + 0.04)  # grafts
        tables.append(tables[-1].apply_delta(ambi.table, pts))
    assert tables[-1].n_leaves > tables[0].n_leaves
    big = np.finfo(np.float32).max
    for d in tables:
        fills = np.asarray(d.leaf_counts)
        assert d.leaf_size == max(int(fills.max(initial=0)), 1)
        assert d.slots == round_up(d.leaf_size, SLOT_TILE)
        assert d.leaf_pts.shape == (2, round_up(d.n_leaves, LEAF_TILE),
                                    d.slots)
        lp, li = np.asarray(d.leaf_pts), np.asarray(d.leaf_ids)
        live = li >= 0
        assert np.array_equal(live.sum(axis=1), fills)
        np.testing.assert_array_equal(
            np.moveaxis(lp[:, : d.n_leaves], 0, -1)[live],
            pts[li[live]].astype(np.float32),
        )
        assert np.all(lp[:, : d.n_leaves][:, ~live] == big)
        assert np.all(lp[:, d.n_leaves:] == big)


def test_apply_delta_requires_scaffolding_after_pytree_roundtrip():
    pts = _f32_points(20_000, 2, 9)
    idx = bulk_load(pts, 250, PageStore(250))
    dev = DeviceTable.from_index(idx)
    leaves, treedef = jax.tree_util.tree_flatten(dev)
    dev2 = jax.tree_util.tree_unflatten(treedef, leaves)
    with pytest.raises(ValueError, match="scaffolding"):
        dev2.apply_delta(idx.table, pts)


# --------------------------------------------------------------------------
# sharded adaptive: refresh touches only changed shards
# --------------------------------------------------------------------------
def test_sharded_adaptive_refreshes_only_changed_shards():
    pts = _f32_points(100_000, 2, 10)
    host = AMBI(pts, 120)
    ambi = AMBI(pts, 120)
    for a in (host, ambi):  # give the root children so the plan can split
        a.window(np.full(2, 0.4), np.full(2, 0.45))
    srv = DeviceQueryServer.from_ambi(ambi, microbatch=8, shards=4)
    m = srv.sdev.m
    boot = srv.upload_stats["full_exports"]
    assert boot == m
    rng = np.random.default_rng(11)
    for step in range(4):
        c = rng.random((8, 2)) * 0.3 + 0.3
        c = c.astype(np.float32).astype(np.float64)
        got = srv.window(c - 0.02, c + 0.02)
        for i in range(8):
            want, _ = host.window(c[i] - 0.02, c[i] + 0.02)
            assert np.array_equal(np.sort(got[i]), np.sort(want)), (step, i)
        gk = srv.knn(c, 8)
        for i in range(8):
            wk, _ = host.knn(c[i], 8)
            assert np.array_equal(gk[i], wk), (step, i)
    # every post-boot export was a targeted shard refresh, and the focused
    # stream touched a strict subset of the shards per refresh round
    extra = srv.upload_stats["full_exports"] - boot
    assert extra == srv.stats.shard_refreshes > 0
    assert extra < m * srv.stats.microbatches
    ambi.table.check_invariants(len(pts))


def test_sharded_adaptive_unrefined_root_boot_replans_to_m_shards():
    """Booting sharded serving from the single-unrefined-root state starts
    with the only possible plan (one whole-table shard) and must *re-plan*
    to the requested shard count once grafts grow the tree — not keep
    full-re-exporting the degenerate shard forever."""
    pts = _f32_points(80_000, 2, 20)
    host = AMBI(pts, 120)
    ambi = AMBI(pts, 120)
    srv = DeviceQueryServer.from_ambi(ambi, microbatch=8, shards=3)
    assert srv.sdev.m == 1  # nothing to cut yet
    rng = np.random.default_rng(21)
    for step in range(4):
        c = (rng.random((8, 2)) * 0.3 + 0.3).astype(np.float32)
        c = c.astype(np.float64)
        got = srv.window(c - 0.02, c + 0.02)
        for i in range(8):
            want, _ = host.window(c[i] - 0.02, c[i] + 0.02)
            assert np.array_equal(np.sort(got[i]), np.sort(want)), (step, i)
    assert srv.sdev.m == 3 and srv.stats.shards == 3
    # post-re-plan refreshes are targeted: total exports = degenerate boot
    # + one m-shard re-plan + the per-changed-shard refreshes after it
    assert srv.upload_stats["full_exports"] == (
        1 + srv.sdev.m + (srv.stats.shard_refreshes - srv.sdev.m)
    )


# --------------------------------------------------------------------------
# compact: vacuum under graft churn (satellite 5)
# --------------------------------------------------------------------------
def _churn_once(seed: int, ops: list[int]) -> None:
    pts = _f32_points(12_000, 2, seed)
    M = 24  # 36 data pages > M: dense root, real adaptive builds
    ambi = AMBI(pts, M)
    fresh = bulk_load(pts, 250, PageStore(250))  # id-parity reference
    rng = np.random.default_rng(seed + 100)
    for op in ops:
        if op == 0:
            c = rng.random(2) * 0.8 + 0.1
            lo, hi = c - 0.05, c + 0.05
            got, _ = ambi.window(lo, hi)
            want, _ = window_query_batch(fresh, lo[None], hi[None])
            assert np.array_equal(np.sort(got), np.sort(want[0]))
        elif op == 1:
            q = rng.random(2).astype(np.float32).astype(np.float64)
            k = int(rng.integers(1, 20))
            got, _ = ambi.knn(q, k)
            want, _ = knn_query_batch(fresh, q[None], k)
            da = np.sum((pts[got] - q) ** 2, axis=1)
            db = np.sum((pts[want[0]] - q) ** 2, axis=1)
            np.testing.assert_array_equal(da, db)
            if len(np.unique(db)) == len(db):
                assert np.array_equal(got, want[0])
        else:
            remap = ambi.table.compact()
            assert ambi.table.n_perm == len(pts)  # vacuum is exact
            assert np.all(remap[remap >= 0] < ambi.table.n_nodes)
        ambi.table.check_invariants(len(pts))
    ambi.table.compact()
    assert ambi.table.n_perm == len(pts)
    # post-compact queries still exact
    got, _ = ambi.window(np.zeros(2), np.ones(2))
    assert len(got) == len(pts)


def test_churn_fixed_seeds():
    _churn_once(0, [0, 1, 2, 0, 0, 1, 2, 1, 0, 2])
    _churn_once(1, [2, 0, 2, 1, 1, 2, 0, 2])


if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(
        seed=st.integers(0, 3),
        ops=st.lists(st.integers(0, 2), min_size=3, max_size=10),
    )
    def test_churn_hypothesis(seed, ops):
        _churn_once(seed, ops)


def test_compact_preserves_serving_scaffolding():
    """Compaction mid-serving: the device table's row maps are rebased and
    subsequent deltas stay consistent."""
    pts = _f32_points(60_000, 2, 12)
    ambi = AMBI(pts, 120)
    srv = DeviceQueryServer.from_ambi(ambi, microbatch=4, compact_slack=0.05)
    rng = np.random.default_rng(13)
    for _ in range(6):
        c = rng.random((4, 2)) * 0.7 + 0.15
        c = c.astype(np.float32).astype(np.float64)
        srv.window(c - 0.03, c + 0.03)
    assert srv.stats.compactions >= 1
    assert ambi.table.n_perm <= 1.05 * len(pts)
    # scaffolding still aligned: leaf slots point at real leaf rows
    t = ambi.table
    assert np.all(t.is_leaf_row(srv.dev.leaf_rows))
    got = srv.window(np.zeros((1, 2)), np.ones((1, 2)))
    assert len(got[0]) == len(pts)


# --------------------------------------------------------------------------
# satellite regressions
# --------------------------------------------------------------------------
def test_device_table_pytree_roundtrip_recovers_n_points():
    """tree_unflatten used to leave n_points=None, crashing
    knn_query_batch_jax's ``min(k, dev.n_points)`` with a TypeError."""
    pts = _f32_points(20_000, 2, 14)
    idx = bulk_load(pts, 250, PageStore(250))
    dev = DeviceTable.from_index(idx)
    leaves, treedef = jax.tree_util.tree_flatten(dev)
    dev2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert dev2.n_points is None
    qs = _f32_points(4, 2, 15)
    got = knn_query_batch_jax(dev2, qs, 2 * len(pts))  # k > n: min() matters
    want = knn_query_batch_jax(dev, qs, 2 * len(pts))
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    assert dev2.live_points() == dev.n_points == len(pts)


def test_retrieval_server_lru_matches_reference_policy():
    """The OrderedDict LRU must replay the old dict+min-scan policy's
    hit/miss stats (and final hot set) bit for bit on a pinned stream."""
    import jax.numpy as jnp

    from repro.core import jax_index
    from repro.core.datasets import osm_like

    pts = osm_like(20_000, seed=3)
    cap = 8
    srv = RetrievalServer(pts, levels=6, adaptive=True, hot_capacity=cap)
    hot: dict[int, int] = {}
    tick = hits = misses = 0
    rng = np.random.default_rng(4)
    for step in range(25):
        width = 0.05 if step % 3 else 1.0  # focused with uniform bursts
        qs = (rng.random((16, 2)) * width + (0.6 if width < 1 else 0.0))
        qs = np.clip(qs, 0, 1).astype(np.float32)
        srv.knn(qs, 4)
        leaves = np.asarray(jax_index.route(srv.index, jnp.asarray(qs)))
        for leaf in leaves:  # the seed policy, verbatim
            tick += 1
            if int(leaf) in hot:
                hits += 1
            else:
                misses += 1
            hot[int(leaf)] = tick
            if len(hot) > cap:
                del hot[min(hot, key=hot.get)]
    assert srv.stats.hot_hits == hits
    assert srv.stats.cold_misses == misses
    assert dict(srv.hot) == hot


def test_ambi_refiner_takes_query_context_explicitly():
    """Refinement triggered outside a query (the serving loop) must flush
    against *that* query's geometry: refiners bound to different corners
    leave different unrefined patterns, and no stale instance state
    remains."""
    pts = _f32_points(60_000, 2, 16)
    a1 = AMBI(pts, 120)
    a2 = AMBI(pts, 120)
    assert not hasattr(a1, "_query_dist")
    lo1, hi1 = np.full(2, 0.02), np.full(2, 0.08)    # corner near origin
    lo2, hi2 = np.full(2, 0.92), np.full(2, 0.98)    # opposite corner
    assert a1.window_refiner(lo1, hi1)(0)
    assert a2.window_refiner(lo2, hi2)(0)
    for a in (a1, a2):
        a.table.check_invariants(len(pts))
        assert bool(a.table.unrefined.any())  # dense root stayed partial

    def unref_boxes(a):
        u = np.flatnonzero(a.table.unrefined)
        return {tuple(np.round(np.concatenate(
            [a.table.mbb_lo[r], a.table.mbb_hi[r]]), 6)) for r in u}

    assert unref_boxes(a1) != unref_boxes(a2)
    # the context that drove refinement keeps its own neighborhood hot:
    # the refined (active) subspaces sit near the bound query corner
    got, _ = a1.window(lo1, hi1)  # answers come straight off refined rows
    assert np.array_equal(np.sort(got), window_oracle(pts, lo1, hi1))

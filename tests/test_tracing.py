"""Spans and counters inside the program: ``repro.tracing``, the
``Frontend``'s queue and hold counters, and the ``query.window`` fields of
the fused window path checked against a NumPy recount."""
import threading
import time

import jax
import numpy as np
import pytest

from repro import tracing
from repro.core import queries_jax as QJ
from repro.core.jax_index import _pow2
from repro.core.queries_jax import DeviceTable
from repro.serve.engine import DeviceQueryServer
from repro.serve.frontend import Frontend, VirtualClock

from engines import build_fmbi, f32_points


@pytest.fixture
def profiler(tmp_path):
    """The JAX profiler traces for the test's body; yields the body's
    start on ``time.monotonic``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield time.monotonic()
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def index():
    return build_fmbi(f32_points(12000, 2, seed=5), M=64)


# -- repro.tracing --------------------------------------------------------
def test_span_records_nothing_while_the_profiler_is_off():
    t0 = time.monotonic()
    with tracing.span("test.off", a=1) as sp:
        sp.set(b=2)
    assert tracing.recorded(t0, time.monotonic()) == []


def test_span_records_names_fields_and_order(profiler):
    with tracing.span("test.outer", a=1) as outer:
        with tracing.span("test.inner", what="x"):
            pass
        outer.set(b=2)

    def worker():
        with tracing.span("test.thread"):
            pass

    th = threading.Thread(target=worker, name="test-worker")
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()
    recs = tracing.recorded(profiler, time.monotonic())
    assert [r.name for r in recs] == ["test.inner", "test.outer",
                                      "test.thread"]
    inner, outer_rec, other = recs
    assert outer_rec.fields == {"a": 1, "b": 2}
    assert inner.fields == {"what": "x"}
    assert (outer_rec.t_start <= inner.t_start <= inner.t_end
            <= outer_rec.t_end)
    assert other.thread == "test-worker" != outer_rec.thread
    assert tracing.recorded(profiler, time.monotonic(), name="test.inner") \
        == [inner]
    # a record counts where it started
    assert tracing.recorded(inner.t_start + 1e-9, time.monotonic(),
                            name="test.inner") == []


# -- Frontend counters --------------------------------------------------
def test_queue_wait_is_the_exact_virtual_wait(index):
    srv = DeviceQueryServer.from_index(index, microbatch=8)
    clock = VirtualClock()
    fe = Frontend(srv, clock=clock, queue_bound=64, batch_max=4,
                  batch_window_s=0.01)
    c = np.array([0.5, 0.5])
    waits = []
    for dt in (0.0, 0.003, 0.004, 0.002):   # four requests: one full batch
        clock.advance(dt)
        fe.submit_window(c - 0.05, c + 0.05)
    t_batch = clock()
    waits += [t_batch - t for t in (0.0, 0.003, 0.007, 0.009)]
    fe.pump()                                # full: dispatched at once
    clock.advance(0.001)
    fe.submit_window(c - 0.1, c + 0.1)       # alone: waits out the window
    clock.advance(0.02)
    fe.pump()
    waits.append(0.02)
    assert fe.stats.batches == 2
    assert fe.stats.dispatched == fe.stats.completed == 5
    assert fe.stats.queue_wait_s == pytest.approx(sum(waits), abs=1e-12)
    assert fe.stats.hold_s == 0.0            # no dispatcher thread here


def test_hold_counts_the_dispatcher_holding_a_queued_request(index):
    srv = DeviceQueryServer.from_index(index, microbatch=8)
    fe = Frontend(srv, queue_bound=64, batch_max=8,
                  batch_window_s=0.02).start()
    try:
        c = np.array([0.3, 0.6])
        reqs = []
        for _ in range(3):                   # sparse: one batch each
            reqs.append(fe.submit_window(c - 0.05, c + 0.05))
            assert reqs[-1].wait(30)
            time.sleep(0.03)
    finally:
        fe.stop()
    st = fe.stats
    assert st.dispatched == 3 and st.batches == 3
    assert st.hold_s > 0
    # every hold is a queued request's wait, and the dispatch stamps it
    assert st.queue_wait_s >= st.hold_s
    assert all(r.t_submit < r.t_dispatch <= r.t_done for r in reqs)


# -- the fused window path's fields -------------------------------------
def _recount(dev, los, his):
    """Per (query, leaf) pair in row-major order, whether the boxes meet
    and how many of the leaf's points lie in the window (NumPy, float32)."""
    n = dev.n_leaves
    leaf_lo = np.asarray(dev.leaf_lo)[:n]
    leaf_hi = np.asarray(dev.leaf_hi)[:n]
    pts = np.moveaxis(np.asarray(dev.leaf_pts), 0, -1)[:n]
    counts = np.asarray(dev.leaf_counts)[:n]
    lo = los.astype(np.float32)
    hi = his.astype(np.float32)
    meet = np.all((leaf_lo[None] <= hi[:, None])
                  & (leaf_hi[None] >= lo[:, None]), axis=2)
    qi, li = np.nonzero(meet)
    live = np.arange(pts.shape[1])[None, :] < counts[li][:, None]
    inside = np.all((pts[li] >= lo[qi][:, None])
                    & (pts[li] <= hi[qi][:, None]), axis=2) & live
    return inside.sum(axis=1)


@pytest.mark.parametrize("chunk", [None, 8])
def test_query_window_fields_match_a_numpy_recount(index, profiler,
                                                   monkeypatch, chunk):
    if chunk is not None:                    # several pair chunks
        monkeypatch.setattr(QJ, "PAIR_CHUNK", chunk)
    dev = DeviceTable.from_index(index)
    rng = np.random.default_rng(7)
    ctr = rng.random((5, 2))
    los, his = ctr - 0.08, ctr + 0.08
    res = QJ._window_batch_fused(dev, los, his, use_kernel=False,
                                 return_cold=False, device_id_pack=True)
    recs = tracing.recorded(profiler, time.monotonic())
    win = [r for r in recs if r.name == "query.window"]
    assert len(win) == 1
    f = win[0].fields
    per_pair = _recount(dev, los, his)
    step = chunk or QJ.PAIR_CHUNK
    chunks = [per_pair[a:a + step] for a in range(0, len(per_pair), step)]
    assert f["q"] == 5
    assert f["pairs"] == len(per_pair) > 0
    assert f["ids"] == per_pair.sum() == sum(len(r) for r in res)
    assert f["chunks"] == len(chunks)
    assert f["pair_slots"] == sum(_pow2(len(c)) for c in chunks)
    assert f["id_slots"] == sum(_pow2(int(c.sum())) for c in chunks
                                if c.sum())
    if chunk is None:
        assert len(chunks) == 1
    else:
        assert len(chunks) > 2
        assert f["pairs"] <= f["pair_slots"] < 2 * f["pairs"]
    # the blocking reads nest inside the batch, each named
    syncs = [r for r in recs if r.name == "query.sync"]
    assert {r.fields["what"] for r in syncs} == {"pairs", "per_query",
                                                 "ids_total", "ids"}
    assert len(syncs) == (1 + 2 * len(chunks)
                          + sum(c.sum() > 0 for c in chunks))
    split = [r for r in recs if r.name == "query.split"]
    assert len(split) == 1
    for r in syncs + split:
        assert win[0].t_start <= r.t_start <= r.t_end <= win[0].t_end

"""The per-layer metrics that read the program's own counters and spans,
on a hand-built context and record list; and what they give on a program
that has neither (the parent of the change that added them)."""
import collections
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as h  # noqa: E402
from repro import tracing  # noqa: E402
from repro.serve.frontend import FrontendStats  # noqa: E402

NEW = ("frontend.queue_ms", "frontend.hold_ms", "query.host_ms.window",
       "query.pair_fill", "query.id_fill")


def _ctx(stats, window=(10.0, 20.0)):
    return h.Context(cell=None, seconds=10.0, records=None, window=window,
                     frontend=stats, engine_calls=[], build={},
                     compiles_in_window=0, trace=None, leaf_lo=None,
                     leaf_hi=None, leaf_size=0, device_kind="")


def _rec(name, t0, t1, thread="frontend-dispatch", **fields):
    return tracing.Record(name, t0, t1, thread, fields)


@pytest.fixture
def records(monkeypatch):
    recs = [
        # before the window: left out
        _rec("query.window", 9.9, 9.95, q=1, pairs=1, pair_slots=1, ids=1,
             id_slots=1),
        _rec("query.sync", 11.001, 11.004, what="pairs"),
        _rec("query.sync", 11.005, 11.006, what="ids"),
        _rec("query.window", 11.0, 11.010, q=2, pairs=20, pair_slots=32,
             ids=900, id_slots=1024),
        _rec("query.sync", 12.002, 12.012, what="pairs"),
        # another thread's read, inside the batch's time: not the batch's
        _rec("query.sync", 12.013, 12.015, thread="other", what="ids"),
        _rec("query.window", 12.0, 12.020, q=1, pairs=10, pair_slots=16,
             ids=100, id_slots=128),
        _rec("query.sync", 13.0, 13.001, what="pairs"),  # in no batch
        _rec("frontend.hold", 11.5, 11.502),
    ]
    monkeypatch.setattr(tracing, "_records", collections.deque(recs))
    return recs


def _read(name, ctx):
    return h.load_reader(name)(ctx)


def test_the_new_metrics_are_in_the_manifest():
    m = {x["name"]: x for x in h.load_manifest(ROOT)["per_layer"]}
    windows = {"osm-10m.window-focused"}
    for name in NEW:
        # the frontend's counters read any cell; the query spans windows
        cells = windows | ({"osm-10m.knn-near"}
                           if name.startswith("frontend.") else set())
        assert set(m[name]["workloads"]) == cells
        assert m[name]["moves"] == "p50_ms"


def test_frontend_counters():
    st = FrontendStats(batches=4, dispatched=5, queue_wait_s=0.030,
                       hold_s=0.006)
    ctx = _ctx(st)
    assert _read("frontend.queue_ms", ctx) == pytest.approx(6.0)
    assert _read("frontend.hold_ms", ctx) == pytest.approx(1.5)
    # the bound the two keep: every hold is part of some request's wait
    assert (_read("frontend.queue_ms", ctx)
            >= _read("frontend.hold_ms", ctx) * st.batches / st.dispatched)
    assert _read("frontend.queue_ms", _ctx(FrontendStats())) is None
    assert _read("frontend.hold_ms", _ctx(FrontendStats())) is None


def test_query_spans(records):
    ctx = _ctx(FrontendStats())
    # batch 1: 10 ms less 3 + 1 ms of reads; batch 2: 20 ms less 10 ms
    assert _read("query.host_ms.window", ctx) == pytest.approx(8.0)
    assert _read("query.pair_fill", ctx) == pytest.approx(100.0 * 30 / 48)
    assert _read("query.id_fill", ctx) == pytest.approx(100.0 * 1000 / 1152)


def test_a_window_without_spans_gives_none(records):
    ctx = _ctx(FrontendStats(), window=(30.0, 40.0))
    for name in NEW[2:]:
        assert _read(name, ctx) is None


def test_no_id_pack_gives_no_id_fill(monkeypatch):
    monkeypatch.setattr(tracing, "_records", collections.deque([
        _rec("query.window", 11.0, 11.01, q=1, pairs=3, pair_slots=4,
             ids=7, id_slots=0)]))
    ctx = _ctx(FrontendStats())
    assert _read("query.id_fill", ctx) is None
    assert _read("query.pair_fill", ctx) == pytest.approx(75.0)


def test_a_program_without_them_gives_none(monkeypatch):
    """The parent has neither ``repro.tracing`` nor the new counters: each
    reader gives None, and none raises."""
    class OldStats:
        batches, completed = 3, 4

    monkeypatch.delattr(sys.modules["repro"], "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    ctx = _ctx(OldStats())
    for name in NEW:
        assert _read(name, ctx) is None

"""The traffic generator: one seed, one workload; shares and counts exact."""
import hashlib
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generator as gen  # noqa: E402
from bench.datasets import f32_exact, make_points  # noqa: E402
from bench.tests import mixes  # noqa: E402

POINTS = f32_exact(np.random.default_rng(0).random((5000, 5)))


def _mixed():
    return mixes.MIXED


def _draw(traffic, seed, n=500):
    reqs = gen.make_requests(traffic, POINTS, n, gen.rng_for(seed, 1))
    offs = gen.arrivals(dict(traffic, rate=50.0), 10.0, gen.rng_for(seed, 2))
    return reqs, offs


def test_same_seed_same_requests():
    a, oa = _draw(_mixed(), 2**31 + 7)
    b, ob = _draw(_mixed(), 2**31 + 7)
    for x, y in zip((a.kind, a.lo, a.hi, a.k, oa), (b.kind, b.lo, b.hi, b.k, ob)):
        assert np.array_equal(x, y)


def test_other_seed_other_requests_same_work():
    a, oa = _draw(_mixed(), 1)
    b, ob = _draw(_mixed(), 2)
    assert not np.array_equal(a.lo, b.lo) and not np.array_equal(oa, ob)
    # the same number of each kind and of arrivals, in another order
    assert np.array_equal(np.bincount(a.kind), np.bincount(b.kind))
    assert len(oa) == len(ob) == 500
    assert np.bincount(a.kind).tolist() == [375, 125]


def test_negative_and_large_seeds():
    for s in (-5, 0, 2**33 + 1):
        reqs, _ = _draw(_mixed(), s, n=10)
        assert len(reqs) == 10


def test_windows_follow_the_mix():
    reqs, _ = _draw(_mixed(), 3)
    win = reqs.kind == 0
    # open dropoff dimensions span the whole range; the rest are narrow
    assert np.all(reqs.lo[win][:, 2:4] == 0) and np.all(reqs.hi[win][:, 2:4] == 1)
    assert np.all(reqs.hi[win][:, :2] - reqs.lo[win][:, :2] <= 0.02 + 1e-6)
    assert np.all(reqs.k[~win] == 16)
    # float32-exact coordinates
    assert np.array_equal(reqs.lo, f32_exact(reqs.lo))


def test_focused_windows_stay_in_the_hot_square():
    t = gen.load_traffic("window-focused")
    pts = f32_exact(np.random.default_rng(1).random((100, 2)))
    reqs = gen.make_requests(t, pts, 400, gen.rng_for(9, 1))
    c = (reqs.lo + reqs.hi) / 2
    assert np.all(np.abs(c - np.array(t["focus"])) <= 0.03 + 1e-6)


def test_stream_is_a_pure_function_of_the_index():
    t = mixes.closed(gen.load_traffic("window-focused"))
    a = gen.RequestStream(t, POINTS[:, :2], gen.rng_for(4, 1))
    b = gen.RequestStream(t, POINTS[:, :2], gen.rng_for(4, 1))
    late = [a.get(i) for i in (5000, 3, 4097)]
    assert [b.get(i) for i in (5000, 3, 4097)][1][1].tolist() == late[1][1].tolist()


def test_open_arrivals_are_sorted_and_inside_the_window():
    _, offs = _draw(_mixed(), 11)
    assert np.all(np.diff(offs) >= 0) and offs.min() >= 0 and offs.max() < 10


# what the cells' mixes sent before drifting hot spots came in: the
# requests and arrivals of a two-second window, and requests 0, 1, 4095,
# 4096 and 9000 of the mix as a closed loop, on 20,000 osm_like points
PINNED = {
    ("window-focused", 0): (40, "071b137de8e9d269", "e17985b8bf748fd2"),
    ("window-focused", 7): (40, "559c28a4498464fe", "0b5bf7f0a79fadf6"),
    ("window-focused", -5): (40, "4c5b4de45715d3dc", "9dff6bf55fe1da68"),
    ("window-focused", 2**31 + 99): (40, "21f2f710160eed13",
                                     "ec458cc81daeade0"),
    ("knn-near", 0): (16000, "e289afb71b26ecd7", "5df9d7637de5e496"),
    ("knn-near", 7): (16000, "19293367b9384729", "961bdf789b5d4d09"),
    ("knn-near", -5): (16000, "4d4e9d82d04ead19", "4840365485f54d11"),
    ("knn-near", 2**31 + 99): (16000, "ca89c8d556b5688f",
                               "50455b0c3cac37a3"),
}


@pytest.fixture(scope="module")
def osm_points():
    return make_points({"generator": "osm_like", "n_points": 20000,
                        "data_seed": 0})


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_the_cells_mixes_send_what_they_sent(osm_points, name, seed):
    t = gen.load_traffic(name)
    offs = gen.arrivals(t, 2.0, gen.rng_for(seed, gen.STREAM_WINDOW, 1))
    r = gen.make_requests(t, osm_points, len(offs),
                          gen.rng_for(seed, gen.STREAM_WINDOW, 0))
    h = hashlib.sha256()
    for a in (r.kind, r.lo, r.hi, r.k, offs):
        h.update(np.ascontiguousarray(a).tobytes())
    stream = gen.RequestStream(mixes.closed(t), osm_points,
                               gen.rng_for(seed, gen.STREAM_WINDOW, 0))
    hs = hashlib.sha256()
    for i in (0, 1, 4095, 4096, 9000):
        kind, lo, hi, k = stream.get(i)
        hs.update(np.asarray([kind, k]).tobytes())
        hs.update(lo.tobytes())
        hs.update(hi.tobytes())
    got = (len(offs), h.hexdigest()[:16], hs.hexdigest()[:16])
    assert got == PINNED[(name, seed)]


def _drift(every_s=5.0):
    return mixes.drifting(gen.load_traffic("window-focused"), every_s)


def test_a_drifting_hot_spot_jumps_to_data_each_period(osm_points):
    t = _drift(5.0)
    offs = gen.arrivals(t, 20.0, gen.rng_for(3, 1))
    spots = gen.hotspots(t, osm_points, gen.rng_for(3, 2))
    reqs = gen.make_requests(t, osm_points, len(offs), gen.rng_for(3, 0),
                             offs, spots)
    at = spots[0].at(offs)
    # each period's centre is a data point, and a new one each period
    rows = {tuple(c) for c in at}
    assert len(rows) == 4
    assert all((osm_points == np.array(c)).all(axis=1).any() for c in rows)
    period = (offs // 5.0).astype(int)
    for p in range(4):
        assert len({tuple(c) for c in at[period == p]}) == 1
    # every window lies around the centre of its own period
    mid = (reqs.lo + reqs.hi) / 2
    assert np.all(np.abs(mid - at) <= 0.03 + 1e-6)


def test_hot_spot_centres_do_not_depend_on_when_they_are_asked(osm_points):
    t = _drift(1.0)
    a = gen.hotspots(t, osm_points, gen.rng_for(5, 2))[0]
    b = gen.hotspots(t, osm_points, gen.rng_for(5, 2))[0]
    late = a.at([300.5, 2.0, 0.0])
    early = np.concatenate([b.at([0.0]), b.at([2.0]), b.at([300.5])])
    assert np.array_equal(late, early[[2, 1, 0]])
    c = gen.hotspots(t, osm_points, gen.rng_for(6, 2))[0]
    assert not np.array_equal(c.at(np.arange(10.0)), a.at(np.arange(10.0)))


def test_a_mix_without_drift_has_no_hot_spots(osm_points):
    t = gen.load_traffic("window-focused")
    rng = gen.rng_for(1, 2)
    before = rng.bit_generator.state
    assert gen.hotspots(t, osm_points, rng) == {}
    assert rng.bit_generator.state == before


def test_a_closed_loop_centres_each_request_by_its_send_time(osm_points):
    t = mixes.closed(_drift(1.0))
    spots = gen.hotspots(t, osm_points, gen.rng_for(8, 2))
    s = gen.RequestStream(t, osm_points, gen.rng_for(8, 1), spots)
    for i, sent in ((0, 0.2), (1, 0.7), (2, 1.2), (5000, 7.9)):
        _, lo, hi, _ = s.get(i, sent)
        centre = spots[0].at([sent])[0]
        assert np.all(np.abs((lo + hi) / 2 - centre) <= 0.03 + 1e-6)
    # the same request sent later lies around a later centre
    again = gen.RequestStream(t, osm_points, gen.rng_for(8, 1), spots)
    _, lo_late, _, _ = again.get(1, 3.5)
    _, lo_now, _, _ = again.get(1, 0.7)
    shift = spots[0].at([3.5])[0] - spots[0].at([0.7])[0]
    assert np.abs(shift).max() > 0.1
    assert np.allclose(lo_late - lo_now, shift, atol=1e-6)


def test_a_drifting_mix_needs_times_and_hot_spots(osm_points):
    with pytest.raises(ValueError, match="drifting"):
        gen.make_requests(_drift(), osm_points, 10, gen.rng_for(1, 1))
    bad = _drift()
    bad["mix"][0]["centre"]["drift"]["to"] = "nowhere"
    with pytest.raises(ValueError, match="drift target"):
        gen.hotspots(bad, osm_points, gen.rng_for(1, 2))

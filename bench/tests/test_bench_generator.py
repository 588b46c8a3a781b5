"""The traffic generator: one seed, one workload; shares and counts exact."""
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generator as gen  # noqa: E402
from bench.datasets import f32_exact  # noqa: E402
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

"""The warm-up's plan reaches each bucket it names, and the sample of
checked answers is a pure function of the seed."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as h  # noqa: E402
from bench import warmup  # noqa: E402

CHUNK = 16384


def _pairs(seed, n=512, mean=620, sd=150):
    rng = np.random.default_rng(seed)
    return np.clip(rng.normal(mean, sd, n), mean - 3 * sd,
                   mean + 3 * sd).round().astype(np.int64)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_every_target_gets_a_batch_in_its_bucket(seed):
    pairs = _pairs(seed)
    rng = np.random.default_rng(seed)
    targets = warmup.targets(pairs, 64, CHUNK)
    # the batch buckets 1..64 all appear; past one chunk every pair bucket
    assert sorted({t[0] for t in targets}) == [1, 2, 4, 8, 16, 32, 64]
    assert {t[5] for t in targets if t[0] == 64} == {
        1 << i for i in range(15)}
    missed = []
    for t in targets:
        q_bucket, q_lo, q_hi, p_min, p_max, b = t
        sel = warmup.pick(pairs, t, CHUNK, rng)
        if sel is None:
            missed.append(t)
            continue
        assert len(set(sel.tolist())) == len(sel)
        assert q_lo <= len(sel) <= q_hi and warmup.pow2(len(sel)) == q_bucket
        assert warmup.last_chunk_bucket(int(pairs[sel].sum()), CHUNK) == b
    # only a bucket at the edge of a batch size's reach, which the pool's
    # windows may not fill, is left to the passes of traffic
    for t in missed:
        own = [u[5] for u in targets if u[0] == t[0]]
        assert t[5] in (min(own), max(own)), t
    assert len(missed) <= 2


def test_single_window_batches_stay_in_their_own_buckets():
    pairs = np.full(100, 600)
    t1 = [t for t in warmup.targets(pairs, 64, CHUNK) if t[0] == 1]
    assert [t[5] for t in t1] == [1024]


def test_pow2_and_last_chunk():
    assert [warmup.pow2(n) for n in (1, 2, 3, 16384, 16385)] == [
        1, 2, 4, 16384, 32768]
    assert warmup.last_chunk_bucket(3 * CHUNK, CHUNK) == CHUNK
    assert warmup.last_chunk_bucket(CHUNK + 1, CHUNK) == 1
    assert warmup.last_chunk_bucket(900, CHUNK) == 1024


def test_sample_keeps_the_least_draws_whatever_the_order():
    def kept(order, size=20):
        s = h.Sample(size, np.random.default_rng(7))
        keep = set()
        for i in order:
            k, ev = s.offer(i)
            if k:
                keep.add(i)
            keep.discard(ev)
        return keep, s

    a, s = kept(range(1000))
    b, _ = kept(np.random.default_rng(1).permutation(1000))
    assert a == b and len(a) == 20
    u = np.array([s.draw(i) for i in range(1000)])
    assert a == set(np.argsort(u)[:20].tolist())

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


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_the_id_plan_adds_the_id_buckets_the_pair_plan_missed(seed):
    rng = np.random.default_rng(seed)
    pairs = _pairs(seed)
    ids = (pairs * rng.uniform(60, 320, len(pairs))).round().astype(np.int64)
    base = warmup.plan(pairs, 64, CHUNK, rng)
    extra = warmup.id_plan(pairs, ids, 64, CHUNK, rng, base)

    def buckets(sel):
        return warmup.pow2(pairs[sel].sum()), warmup.pow2(ids[sel].sum())

    had = {buckets(sel) for sel in base if pairs[sel].sum() <= CHUNK}
    new = [buckets(sel) for sel in extra]
    want = {(t[5], r) for t, r in warmup.id_targets(pairs, ids, 64, CHUNK)}
    # each added batch is of one chunk and reaches a (pc, r) of its own
    assert new and len(set(new)) == len(new) and not set(new) & had
    assert set(new) <= want
    for sel in extra:
        assert len(set(sel.tolist())) == len(sel) <= 64
    # only buckets at the edge of the reach are left to the passes
    assert len(want - had - set(new)) <= 0.15 * len(want)


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


@pytest.fixture(scope="module")
def small_index():
    """A 20k-point 5-D index on the CPU, and uniform k-NN queries over it:
    in five dimensions most of them go through escalation rounds."""
    from bench.datasets import f32_exact, make_points

    config = {"generator": "nycyt_like", "n_points": 20_000, "dim": 5,
              "data_seed": 0, "buffer_fraction": 0.05}
    points = make_points(config)
    server, _ = h.build_and_boot(config, points)
    qs = f32_exact(np.random.default_rng(3).random((256, 5)))
    return server.dev, points, qs


def _escalations(monkeypatch):
    """``(Q, budget, p)`` of every escalation round the engine runs."""
    from repro.core import queries_jax as qj

    seen, last = set(), {}
    pending, core = qj._knn_pending, qj._knn_core_fused

    def spy_pending(qs, exact, b0, p):
        last["qp"] = (qs.shape[0], p)
        return pending(qs, exact, b0, p)

    def spy_core(dev, qs, b0, k, n_candidate_leaves, use_kernel):
        if "qp" in last:
            q_bucket, p = last.pop("qp")
            assert qs.shape[0] == p
            seen.add((q_bucket, n_candidate_leaves, p))
        return core(dev, qs, b0, k, n_candidate_leaves, use_kernel)

    monkeypatch.setattr(qj, "_knn_pending", spy_pending)
    monkeypatch.setattr(qj, "_knn_core_fused", spy_core)
    return seen


def test_the_knn_plan_reaches_every_escalation_bucket(small_index,
                                                      monkeypatch):
    from bench import reference, work
    from repro.core import queries_jax as qj

    dev, points, qs = small_index
    k, batch_max = 16, 16
    n_leaves = dev.n_leaves
    lo = np.asarray(dev.leaf_lo)[:n_leaves]
    hi = np.asarray(dev.leaf_hi)[:n_leaves]
    ref = reference.BruteForce(points)
    kth = np.array([ref.knn_dist2(q, k)[-1] for q in qs])
    c0 = warmup.first_budget(k, int(dev.leaf_size), n_leaves)
    need = warmup.knn_rounds(work.knn_leaves_each(lo, hi, qs, kth), c0,
                             n_leaves)
    assert need.max() >= 2 and (need == 0).any()
    targets = warmup.knn_targets(need, batch_max)
    assert {i for _, i, _ in targets} == set(range(1, need.max() + 1))
    seen = _escalations(monkeypatch)
    batches = warmup.knn_plan(need, batch_max)
    # one batch of each size for the first round alone
    assert [len(b) for b in batches[:batch_max]] == list(
        range(1, batch_max + 1))
    for sel in batches:
        qj.knn_query_batch_jax(dev, qs[sel], k, use_kernel=False)
    cap = warmup.pow2(n_leaves)
    want = {(q_bucket, min(c0 << i, cap), p) for q_bucket, i, p in targets}
    assert want <= seen, sorted(want - seen)

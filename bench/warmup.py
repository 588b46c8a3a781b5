"""The warm-up: build, before the window, every program that the cell's
requests can reach, by the buckets the engine pads to.

The served window path compiles one program per bucket of each shape it
sees (``repro.core.queries_jax``):

- the batch, padded to a power of two ``Q`` (1 to ``BATCH_MAX``);
- the (window, leaf) candidate pairs of the batch, scanned in chunks of
  ``PAIR_CHUNK``, each padded to a power of two ``pc``: a batch of ``P``
  pairs gives full chunks and a last one of ``P mod PAIR_CHUNK`` pairs, so
  any ``pc`` can follow once ``P`` passes one chunk;
- the qualifying ids of each chunk, packed into a power of two ``r``: the
  id pack has one program per ``(pc, r)``.

A batch's pairs are the leaves whose boxes meet its windows, counted here
from the index's leaf boxes (:func:`bench.work.window_pairs_each`), and its
ids the points inside its windows, counted from the raw points
(:func:`ids_each`).  So the plan draws a pool of the cell's own windows,
and for every batch bucket ``Q`` and every pair bucket ``pc`` that ``Q``
can reach with these windows, picks a batch of them whose pairs land in
that bucket; then, for every id bucket ``r`` that a batch of one chunk can
reach, a batch whose pairs land in ``pc`` and whose ids land in ``r``.  A
batch of several chunks splits its ids between them by where its pairs
fall, which the plan does not model: the passes of the cell's traffic that
set-up runs after the plan catch any bucket the plan missed.

The served k-NN path runs a first round over ``c0`` candidate leaves for a
batch padded to ``Q``, then escalation rounds: round ``i`` reruns the
queries whose exactness certificate failed, packed into a power of two
``p``, over ``c0 * 2**i`` leaves, with programs keyed by ``(Q, p)`` and
``(p, c0 * 2**i)``.  A query's certificate holds once its budget covers
every leaf whose box lies within its k-th distance (the reference's,
:func:`bench.work.knn_leaves_each`), so each query of a pool needs a known
number of rounds; for every ``(Q, round, p)`` that a batch of ``Q`` can
reach, the plan makes a batch of ``p`` queries that need that many rounds
and others that need none.

Reach is judged within four standard deviations of a batch's mean.  The
plan's batches run one after another.
"""
from __future__ import annotations

import numpy as np

from bench import generator as gen
from bench import work

POOL = 512          # requests drawn for the plan
KNN_POOL = 1024     # k-NN requests drawn to learn how many rounds they need
STARTS = 4          # random batches the id plan searches from, per bucket


def pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pair_chunk() -> int:
    from repro.core import queries_jax

    return int(queries_jax.PAIR_CHUNK)


def last_chunk_bucket(total: int, chunk: int) -> int:
    """The pair bucket of a batch's last chunk."""
    rem = total % chunk
    return pow2(rem) if rem else chunk


def reach(pairs: np.ndarray, q: int) -> tuple[int, int]:
    """The fewest and most pairs that a batch of ``q`` windows drawn from
    these holds, within four standard deviations of the mean."""
    s = np.sort(pairs)
    mu, sd = float(pairs.mean()), float(pairs.std())
    spread = 4.0 * sd * np.sqrt(q)
    return (int(max(q * mu - spread, s[:q].sum())),
            int(min(q * mu + spread, s[::-1][:q].sum())))


def targets(pairs: np.ndarray, batch_max: int, chunk: int) -> list:
    """Every ``(Q, q_lo, q_hi, p_min, p_max, pc)``: batches of ``q_lo`` to
    ``q_hi`` windows, padded to ``Q``, hold ``p_min`` to ``p_max`` pairs,
    and can put their last chunk in bucket ``pc``."""
    out = []
    q_bucket = 1
    while q_bucket <= min(batch_max, len(pairs)):
        q_lo, q_hi = max(q_bucket // 2 + 1, 1), q_bucket
        p_min, p_max = reach(pairs, q_lo)[0], reach(pairs, q_hi)[1]
        b = 1
        while b <= chunk:
            # alone, [b/2 + 1, b] pairs; past full chunks, any remainder
            if (b >= p_min and b // 2 + 1 <= min(p_max, chunk)) or p_max > chunk:
                out.append((q_bucket, q_lo, q_hi, p_min, p_max, b))
            b *= 2
        q_bucket *= 2
    return out


def pick(pairs: np.ndarray, target: tuple, chunk: int,
         rng: np.random.Generator, tries: int = 200):
    """Indices of windows whose batch meets ``target`` (see
    :func:`targets`), or None."""
    _, q_lo, q_hi, p_min, p_max, b = target
    n = len(pairs)
    mean = float(pairs.mean())
    # the totals that put the last chunk in b: alone, or past m full chunks
    totals = [(m * chunk + b // 2 + 1, m * chunk + b)
              for m in range(p_max // chunk + 1)]
    if b == chunk:
        totals += [(m * chunk, m * chunk) for m in range(1, p_max // chunk + 1)]
    totals = [(max(a, p_min), min(z, p_max)) for a, z in totals]
    totals = [t for t in totals if t[0] <= t[1]]
    middle = (p_min + p_max) / 2
    totals.sort(key=lambda t: abs((t[0] + t[1]) / 2 - middle))
    for t_lo, t_hi in totals:
        q = int(np.clip(round((t_lo + t_hi) / 2 / mean), q_lo, min(q_hi, n)))
        sel = _swap_into(rng.choice(n, size=q, replace=False),
                         [(pairs, t_lo, t_hi)], tries)
        if sel is not None:
            return sel
    return None


def _swap_into(sel: np.ndarray, goals: list, tries: int):
    """Swap windows of the batch ``sel`` for others of the pool, one at a
    time, until each total ``values[sel].sum()`` of ``goals`` (a list of
    ``(values, lo, hi)``) lies in its ``[lo, hi]``; None where no swap
    brings the batch nearer."""
    n = len(goals[0][0])

    def miss(totals):
        # how far each total lies outside its range, in units of the range
        return sum(np.maximum(np.maximum(lo - t, t - hi), 0) / (hi - lo + 1)
                   for t, (_, lo, hi) in zip(totals, goals))

    sums = [int(v[sel].sum()) for v, _, _ in goals]
    for _ in range(tries):
        now = miss(sums)
        if now == 0:
            return sel
        inside = np.zeros(n, dtype=bool)
        inside[sel] = True
        out_idx = np.flatnonzero(~inside)
        if not len(out_idx):
            return None
        deltas = [v[out_idx][None, :] - v[sel][:, None] for v, _, _ in goals]
        after = miss([s + d for s, d in zip(sums, deltas)])
        i, j = np.unravel_index(np.argmin(after), after.shape)
        if after[i, j] >= now:
            return None
        sel = sel.copy()
        sel[i] = out_idx[j]
        sums = [s + int(d[i, j]) for s, d in zip(sums, deltas)]
    return None


def plan(pairs: np.ndarray, batch_max: int, chunk: int,
         rng: np.random.Generator) -> list:
    """One batch (indices into the pool) for each target it can meet."""
    batches = []
    for t in targets(pairs, batch_max, chunk):
        sel = pick(pairs, t, chunk, rng)
        if sel is not None:
            batches.append(sel)
    return batches


def ids_each(points: np.ndarray, los: np.ndarray,
             his: np.ndarray) -> np.ndarray:
    """Each window's qualifying points, counted from the raw points."""
    box = np.all((points >= los.min(axis=0)) & (points <= his.max(axis=0)),
                 axis=1)
    sub = points[box]
    sub = sub[np.argsort(sub[:, 0], kind="stable")]
    x = sub[:, 0]
    out = np.zeros(len(los), dtype=np.int64)
    for i, (lo, hi) in enumerate(zip(los, his)):
        s = slice(int(np.searchsorted(x, lo[0], side="left")),
                  int(np.searchsorted(x, hi[0], side="right")))
        out[i] = np.count_nonzero(np.all((sub[s] >= lo) & (sub[s] <= hi),
                                         axis=1))
    return out


def id_targets(pairs: np.ndarray, ids: np.ndarray, batch_max: int,
               chunk: int) -> list:
    """Every ``(target, r)``: a pair target of :func:`targets` that a
    batch of one chunk can meet, and an id bucket ``r`` that such a batch
    can reach: its ids lie within the reach of its windows' ids, and
    within its pairs times the reach of their ids per pair."""
    per_pair = ids / np.maximum(pairs, 1)
    mu, sd = float(per_pair.mean()), float(per_pair.std())
    out = []
    for t in targets(pairs, batch_max, chunk):
        _, q_lo, q_hi, p_min, p_max, b = t
        t_lo, t_hi = max(b // 2 + 1, p_min), min(b, p_max, chunk)
        if t_lo > t_hi:
            continue
        spread = 4.0 * sd / np.sqrt(q_lo)
        i_lo = max(reach(ids, q_lo)[0], t_lo * max(mu - spread,
                                                   per_pair.min()), 1)
        i_hi = min(reach(ids, q_hi)[1], t_hi * min(mu + spread,
                                                   per_pair.max()))
        r = pow2(int(i_lo))
        while r // 2 + 1 <= i_hi:
            out.append((t, r))
            r *= 2
    return out


def id_plan(pairs: np.ndarray, ids: np.ndarray, batch_max: int, chunk: int,
            rng: np.random.Generator, batches: list) -> list:
    """A batch for each ``(pc, r)`` of :func:`id_targets` that none of
    ``batches`` reaches already, searched from up to ``STARTS`` random
    batches."""
    done = set()
    for sel in batches:
        p = int(pairs[sel].sum())
        if 0 < p <= chunk and ids[sel].sum():
            done.add((pow2(p), pow2(int(ids[sel].sum()))))
    out = []
    n = len(pairs)
    for t, r in id_targets(pairs, ids, batch_max, chunk):
        _, q_lo, q_hi, p_min, p_max, b = t
        if (b, r) in done:
            continue
        t_lo, t_hi = max(b // 2 + 1, p_min), min(b, p_max, chunk)
        i_lo, i_hi = r // 2 + 1, r
        q = (t_lo + t_hi) / 2 / pairs.mean() + (i_lo + i_hi) / 2 / ids.mean()
        q = int(np.clip(round(q / 2), q_lo, min(q_hi, n)))
        for _ in range(STARTS):
            sel = _swap_into(rng.choice(n, size=q, replace=False),
                             [(pairs, t_lo, t_hi), (ids, i_lo, i_hi)], 200)
            if sel is not None:
                done.add((b, r))
                out.append(sel)
                break
    return out


def first_budget(k: int, leaf_size: int, n_leaves: int) -> int:
    """The candidate leaves of a k-NN batch's first round, as
    ``queries_jax._knn_batch_fused`` sets them by default."""
    return min(pow2(max(8, -(-2 * k // leaf_size))), pow2(n_leaves))


def knn_rounds(leaves: np.ndarray, c0: int, n_leaves: int) -> np.ndarray:
    """The escalation rounds each query needs: its budget starts at ``c0``
    leaves and doubles until it covers the ``leaves`` within its k-th
    distance, or the whole table."""
    out = np.zeros(len(leaves), dtype=np.int64)
    for i, m in enumerate(leaves):
        c = c0
        while c < m and c < n_leaves:
            c = min(c * 2, pow2(n_leaves))
            out[i] += 1
    return out


def count_reach(f: float, q: int) -> tuple[int, int]:
    """The fewest and most of ``q`` draws that fall in a part of the pool
    of share ``f``, within four standard deviations of the mean."""
    sd = 4.0 * np.sqrt(q * f * (1.0 - f))
    return int(max(np.floor(q * f - sd), 0)), int(min(np.ceil(q * f + sd), q))


def knn_targets(need: np.ndarray, batch_max: int) -> list:
    """Every ``(Q, i, p)``: a batch padded to ``Q`` in which the queries
    still failing at escalation round ``i`` pack into bucket ``p``."""
    out = []
    q_bucket = 1
    while q_bucket <= batch_max:
        q_lo, q_hi = q_bucket // 2 + 1, q_bucket
        for i in range(1, int(need.max(initial=0)) + 1):
            f = float((need >= i).mean())
            lo, hi = count_reach(f, q_lo)[0], count_reach(f, q_hi)[1]
            p = 1
            while p <= q_bucket:
                if p >= lo and p // 2 + 1 <= hi:
                    out.append((q_bucket, i, p))
                p *= 2
        q_bucket *= 2
    return out


def knn_plan(need: np.ndarray, batch_max: int) -> list:
    """Batches (indices into the pool, repeated where the pool has too few):
    one of each size up to ``batch_max``, and for every target of
    :func:`knn_targets` one of ``p`` queries that need the target's rounds
    and others that need none.  Such a batch fails ``p`` certificates in
    each round up to its queries' need, so it covers those rounds' targets
    too."""
    zero = np.flatnonzero(need == 0)
    # the first round alone, at every batch size: the engine cuts the
    # answers to the batch's own size with one small program per size
    base = zero if len(zero) else np.arange(len(need))
    batches, done = [np.resize(base, q) for q in range(1, batch_max + 1)], set()
    for q_bucket, i, p in sorted(knn_targets(need, batch_max),
                                 key=lambda t: -t[1]):
        if (q_bucket, i, p) in done or not len(zero):
            continue
        rounds = int(need[need >= i].min())
        fail = np.flatnonzero(need == rounds)
        q = max(p, q_bucket // 2 + 1)
        batches.append(np.concatenate([np.resize(fail, p),
                                       np.resize(zero, q - p)]))
        done.update((q_bucket, j, p) for j in range(1, rounds + 1))
    return batches


def pool_times(spots: dict, n: int) -> np.ndarray:
    """Times that put each of ``n`` requests in a period of its own, so
    that a pool of a drifting mix sees as many places of its hot spots
    (:func:`bench.generator.hotspots`) as it has requests."""
    return np.arange(n) * max((s.every_s for s in spots.values()),
                              default=0.0)


def run(server, traffic: dict, points: np.ndarray, rng: np.random.Generator,
        leaf_lo: np.ndarray, leaf_hi: np.ndarray, batch_max: int) -> dict:
    """Serve the plan's batches of the cell's window and k-NN requests."""
    spots = gen.hotspots(traffic, points, rng)
    reqs = gen.make_requests(traffic, points, POOL, rng,
                             pool_times(spots, POOL), spots)
    win = np.flatnonzero(reqs.kind == 0)
    batches = []
    if len(win):
        lo, hi = reqs.lo[win], reqs.hi[win]
        pairs = work.window_pairs_each(leaf_lo, leaf_hi, lo, hi)
        ids = ids_each(points, lo, hi)
        chunk = pair_chunk()
        sels = plan(pairs, batch_max, chunk, rng)
        sels += id_plan(pairs, ids, batch_max, chunk, rng, sels)
        batches += [("window", lo[sel], hi[sel], 0) for sel in sels]
    if np.any(reqs.kind == 1):
        from bench import reference

        knn = gen.make_requests(traffic, points, KNN_POOL, rng,
                                pool_times(spots, KNN_POOL), spots)
        ref = reference.BruteForce(points)
        n_leaves = len(leaf_lo)
        for k in sorted(set(knn.k[knn.kind == 1].tolist())):
            qs = knn.lo[(knn.kind == 1) & (knn.k == k)]
            kth = np.array([ref.knn_dist2(q, k)[-1] for q in qs])
            leaves = work.knn_leaves_each(leaf_lo, leaf_hi, qs, kth)
            c0 = first_budget(k, int(server.dev.leaf_size), n_leaves)
            need = knn_rounds(leaves, c0, n_leaves)
            batches += [("knn", qs[sel], None, int(k))
                        for sel in knn_plan(need, batch_max)]
        del ref

    for kind, a, b, k in batches:
        if kind == "window":
            server.window(a, b)
        else:
            server.knn(a, k)
    return {"planned_batches": len(batches)}

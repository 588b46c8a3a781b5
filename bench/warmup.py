"""The warm-up: build, before the window, every program that the cell's
windows can reach, by the buckets the engine pads to.

The served window path compiles one program per bucket of each shape it
sees (``repro.core.queries_jax``):

- the batch, padded to a power of two ``Q`` (1 to ``BATCH_MAX``);
- the (window, leaf) candidate pairs of the batch, scanned in chunks of
  ``PAIR_CHUNK``, each padded to a power of two ``pc``: a batch of ``P``
  pairs gives full chunks and a last one of ``P mod PAIR_CHUNK`` pairs, so
  any ``pc`` can follow once ``P`` passes one chunk;
- the qualifying ids of each chunk, packed into a power of two ``r``.

A batch's pairs are the leaves whose boxes meet its windows, counted here
from the index's leaf boxes (:func:`bench.work.window_pairs_each`).  So the
plan draws a pool of the cell's own windows, and for every batch bucket
``Q`` and every pair bucket ``pc`` that ``Q`` can reach with these
windows, picks a batch of them whose pairs land in that bucket.  The ids
bucket of each chunk follows from the windows picked; the passes of the
cell's traffic that set-up runs after the plan catch any the plan missed.
The plan's batches run one after another: each scan holds a lane-padded
copy of the leaf table, and two at once would not fit the chip.
"""
from __future__ import annotations

import numpy as np

from bench import generator as gen
from bench import work

POOL = 512          # windows drawn for the plan


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
        sel = rng.choice(n, size=q, replace=False)
        total = int(pairs[sel].sum())
        for _ in range(tries):
            if t_lo <= total <= t_hi:
                return sel
            inside = np.zeros(n, dtype=bool)
            inside[sel] = True
            out_idx = np.flatnonzero(~inside)
            if not len(out_idx):
                break
            # the one swap that brings the total nearest the middle
            delta = pairs[out_idx][None, :] - pairs[sel][:, None]
            goal = (t_lo + t_hi) / 2 - total
            i, j = np.unravel_index(np.argmin(np.abs(delta - goal)),
                                    delta.shape)
            if delta[i, j] == 0:
                break
            sel[i] = out_idx[j]
            total += int(delta[i, j])
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


def run(server, traffic: dict, points: np.ndarray, rng: np.random.Generator,
        leaf_lo: np.ndarray, leaf_hi: np.ndarray, batch_max: int) -> dict:
    """Serve the plan's batches of the cell's window requests, and one
    batch of each size bucket of its other requests."""
    reqs = gen.make_requests(traffic, points, POOL, rng)
    win = np.flatnonzero(reqs.kind == 0)
    batches = []
    if len(win):
        lo, hi = reqs.lo[win], reqs.hi[win]
        pairs = work.window_pairs_each(leaf_lo, leaf_hi, lo, hi)
        for sel in plan(pairs, batch_max, pair_chunk(), rng):
            batches.append(("window", lo[sel], hi[sel], 0))
    for k in sorted(set(reqs.k[reqs.kind == 1].tolist())):
        qs = reqs.lo[(reqs.kind == 1) & (reqs.k == k)]
        q = 1
        while q <= batch_max:
            take = np.resize(np.arange(len(qs)), q)
            batches.append(("knn", qs[take], None, int(k)))
            q *= 2

    for kind, a, b, k in batches:
        if kind == "window":
            server.window(a, b)
        else:
            server.knn(a, k)
    return {"planned_batches": len(batches)}

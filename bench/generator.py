"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its requests and arrivals from a
seed.  A new mix is a new data file; this code does not change.

A traffic file holds:

- ``loop``: ``"open"`` (arrivals on a schedule, whatever the answers do)
  or ``"closed"`` (``outstanding`` requests in flight, each answered one
  replaced at once);
- ``rate``: offered requests per second of an open loop, as a number;
- ``outstanding``: requests a closed loop keeps in flight;
- ``queue_bound``: the ``Frontend``'s admission bound;
- ``mix``: request kinds with their ``share`` (see :func:`make_requests`);
- ``focus``: the middle of a focused mix's hot spot, one coordinate per
  dimension (a place the configuration's data fills).  A focus centre
  may carry ``"drift": {"every_s": s, "to": "data"}``: the hot spot then
  moves, and at the start of each ``s``-second period it jumps to a data
  point drawn from the seed (:class:`HotSpot`); each request is centred
  by the period its due time (open loop) or send time (closed loop)
  falls in;
- ``check``: how many of the window's answers the run compares with the
  reference, drawn from the seed;
- ``warmup_seconds`` and ``warmup_passes``: the length of one warm-up
  pass, and the most passes that set-up makes.

Open-loop arrivals are a Poisson process conditioned on its count: exactly
``round(rate * seconds)`` requests, due at uniformly drawn instants, so
every seed offers the same work in another order.  The same holds for the
mix: each kind gets its share of the requests, in an order drawn from the
seed.  A drifting hot spot draws its places from a stream of its own, so
a mix without ``drift`` draws the same requests whether or not the
generator knows of drift.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

from bench.datasets import f32_exact

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"

# the streams one run draws from the seed; the warm-up's stream is apart
# from the window's, so set-up never replays the measured requests
STREAM_WINDOW, STREAM_WARMUP, STREAM_CHECK = 1, 2, 3


def rng_for(seed: int, stream: int, *more: int) -> np.random.Generator:
    """A generator for one stream of ``seed``; any whole number works."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream, *more])


def load_traffic(name: str, traffic_dir: pathlib.Path = TRAFFIC_DIR) -> dict:
    return json.loads((traffic_dir / f"{name}.json").read_text())


@dataclasses.dataclass
class Requests:
    """A block of requests in arrival order.  ``kind`` 0 is a window
    ``[lo, hi]``; 1 is a k-NN query at ``lo`` (``hi`` unused) for ``k``."""

    kind: np.ndarray
    lo: np.ndarray
    hi: np.ndarray
    k: np.ndarray

    def __len__(self) -> int:
        return len(self.kind)


class HotSpot:
    """Where a drifting focus lies over time: period ``p``, the
    ``every_s`` seconds from ``p * every_s``, is centred on the ``p``-th
    data point drawn from ``rng``.  The draws are made in blocks of
    ``BLOCK`` periods as they are asked for, so a period's centre does not
    depend on when it was first asked for."""

    BLOCK = 64

    def __init__(self, drift: dict, points: np.ndarray,
                 rng: np.random.Generator):
        if drift.get("to") != "data":
            raise ValueError(f"unknown drift target {drift.get('to')!r}")
        self.every_s = float(drift["every_s"])
        if not self.every_s > 0:
            raise ValueError(f"drift every_s must be positive: {drift!r}")
        self.points, self.rng = points, rng
        self.rows = np.zeros(0, dtype=np.int64)

    def at(self, t) -> np.ndarray:
        """The centre at each time ``t``, in seconds from the start."""
        p = (np.maximum(np.asarray(t, dtype=np.float64), 0.0)
             // self.every_s).astype(np.int64)
        while p.max(initial=-1) >= len(self.rows):
            self.rows = np.concatenate([self.rows, self.rng.integers(
                len(self.points), size=self.BLOCK)])
        return self.points[self.rows[p]]


def _drifts(spec: dict) -> bool:
    return "drift" in spec.get("centre", {})


def hotspots(traffic: dict, points: np.ndarray,
             rng: np.random.Generator) -> dict:
    """A :class:`HotSpot` for each mix entry whose focus drifts, by the
    entry's index, each on a child stream of ``rng``.  A mix without drift
    gets none, and nothing is drawn."""
    drifting = [i for i, m in enumerate(traffic["mix"]) if _drifts(m)]
    if not drifting:
        return {}
    if rng is None:
        raise ValueError("a drifting mix needs a stream for its hot spots")
    return {i: HotSpot(traffic["mix"][i]["centre"]["drift"], points, child)
            for i, child in zip(drifting, rng.spawn(len(drifting)))}


def _centres(spec: dict, n: int, traffic: dict, points: np.ndarray,
             rng: np.random.Generator) -> np.ndarray:
    """``centre: {"from": "focus", "side": s}`` draws uniformly from the
    s-wide square around ``traffic["focus"]`` (around the origin where the
    focus drifts: the hot spot is added once a request's time is known);
    ``{"from": "data"}`` takes data points, moved by up to ``jitter`` in
    each dimension."""
    src = spec["centre"]
    d = points.shape[1]
    if src["from"] == "focus":
        side = float(src["side"])
        focus = np.zeros(d) if _drifts(spec) else np.asarray(
            traffic["focus"], dtype=np.float64)
        return focus - side / 2 + rng.random((n, d)) * side
    if src["from"] == "data":
        if _drifts(spec):
            raise ValueError("only a focus centre drifts")
        c = points[rng.integers(len(points), size=n)]
        j = float(src.get("jitter", 0.0))
        if j:
            c = c + rng.uniform(-j, j, size=(n, d))
        return c
    raise ValueError(f"unknown centre {src!r}")


@dataclasses.dataclass
class Draws:
    """A block of requests as drawn from the seed, before they are placed:
    each one's mix entry and its centre.  For an entry whose focus drifts
    the centre is an offset from the hot spot, which is known only once
    the request's time is."""

    mix: list
    entry: np.ndarray
    centre: np.ndarray

    def place(self, idx=None, times=None, spots=None) -> Requests:
        """Requests ``idx`` (all by default), those of a drifting entry
        centred by where ``spots`` (from :func:`hotspots`) lie at their
        ``times``."""
        idx = np.arange(len(self.entry)) if idx is None else np.asarray(idx)
        entry, c = self.entry[idx], self.centre[idx].copy()
        n, d = c.shape
        kind = np.zeros(n, dtype=np.int8)
        lo = np.zeros((n, d))
        hi = np.ones((n, d))
        k = np.zeros(n, dtype=np.int64)
        for i, spec in enumerate(self.mix):
            sel = np.flatnonzero(entry == i)
            if _drifts(spec) and len(sel):
                if times is None or spots is None:
                    raise ValueError("a drifting focus needs the requests' "
                                     "times and its hot spots")
                c[sel] += spots[i].at(np.asarray(times)[sel])
            ci = np.clip(c[sel], 0.0, 1.0)
            if spec["kind"] == "window":
                hw = spec["half_width"]
                if len(hw) != d:
                    raise ValueError(f"half_width has {len(hw)} entries for "
                                     f"{d}-dimensional data")
                for j, h in enumerate(hw):
                    if h is not None:
                        lo[sel, j] = np.clip(ci[:, j] - h, 0.0, 1.0)
                        hi[sel, j] = np.clip(ci[:, j] + h, 0.0, 1.0)
            elif spec["kind"] == "knn":
                kind[sel] = 1
                lo[sel] = ci
                k[sel] = int(spec["k"])
            else:
                raise ValueError(f"unknown request kind {spec['kind']!r}")
        return Requests(kind, f32_exact(lo), f32_exact(hi), k)


def draw_requests(traffic: dict, points: np.ndarray, n: int,
                  rng: np.random.Generator) -> Draws:
    """``n`` requests of the mix, drawn but not yet placed (see
    :func:`make_requests`)."""
    mix = traffic["mix"]
    d = points.shape[1]
    shares = np.array([float(m["share"]) for m in mix])
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[np.argmax(shares)] += n - counts.sum()
    which = rng.permutation(np.repeat(np.arange(len(mix)), counts))
    centre = np.zeros((n, d))
    for i, spec in enumerate(mix):
        sel = np.flatnonzero(which == i)
        centre[sel] = _centres(spec, len(sel), traffic, points, rng)
    return Draws(mix, which, centre)


def make_requests(traffic: dict, points: np.ndarray, n: int,
                  rng: np.random.Generator, times=None,
                  spots=None) -> Requests:
    """``n`` requests of the mix.  A window entry gives ``half_width``, one
    per dimension; ``null`` leaves that dimension open (the whole [0, 1]
    range).  A k-NN entry gives ``k``.  Coordinates are float32-exact.
    Where the focus drifts, request i is centred by where ``spots`` (from
    :func:`hotspots`) lie at ``times[i]``."""
    return draw_requests(traffic, points, n, rng).place(None, times, spots)


def arrivals(traffic: dict, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due offsets in [0, seconds) of an open loop, ascending."""
    n = int(round(float(traffic["rate"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


class RequestStream:
    """Requests made on demand, in blocks, from one stream of the seed:
    the i-th request is the same whatever the loop asks for after it, and
    where the focus drifts it is centred by the time it is sent."""

    BLOCK = 4096

    def __init__(self, traffic: dict, points: np.ndarray,
                 rng: np.random.Generator, spots=None):
        self.traffic, self.points, self.rng = traffic, points, rng
        self.spots = spots or {}
        self.blocks: list = []  # Requests, or Draws where the focus drifts

    def get(self, i: int, t: float = 0.0
            ) -> tuple[int, np.ndarray, np.ndarray, int]:
        b, j = divmod(i, self.BLOCK)
        while len(self.blocks) <= b:
            block = draw_requests(self.traffic, self.points, self.BLOCK,
                                  self.rng)
            self.blocks.append(block if self.spots else block.place())
        r = self.blocks[b]
        if self.spots:
            r, j = r.place([j], [t], self.spots), 0
        return int(r.kind[j]), r.lo[j], r.hi[j], int(r.k[j])

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
  dimension (a place the configuration's data fills);
- ``check``: how many of the window's answers the run compares with the
  reference, drawn from the seed;
- ``warmup_seconds`` and ``warmup_passes``: the length of one warm-up
  pass, and the most passes that set-up makes.

Open-loop arrivals are a Poisson process conditioned on its count: exactly
``round(rate * seconds)`` requests, due at uniformly drawn instants, so
every seed offers the same work in another order.  The same holds for the
mix: each kind gets its share of the requests, in an order drawn from the
seed.
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


def _centres(spec: dict, n: int, traffic: dict, points: np.ndarray,
             rng: np.random.Generator) -> np.ndarray:
    """``centre: {"from": "focus", "side": s}`` draws uniformly from the
    s-wide square around ``traffic["focus"]``; ``{"from": "data"}`` takes
    data points, moved by up to ``jitter`` in each dimension."""
    src = spec["centre"]
    d = points.shape[1]
    if src["from"] == "focus":
        side = float(src["side"])
        focus = np.asarray(traffic["focus"], dtype=np.float64)
        return focus - side / 2 + rng.random((n, d)) * side
    if src["from"] == "data":
        c = points[rng.integers(len(points), size=n)]
        j = float(src.get("jitter", 0.0))
        if j:
            c = c + rng.uniform(-j, j, size=(n, d))
        return c
    raise ValueError(f"unknown centre {src!r}")


def make_requests(traffic: dict, points: np.ndarray, n: int,
                  rng: np.random.Generator) -> Requests:
    """``n`` requests of the mix.  A window entry gives ``half_width``, one
    per dimension; ``null`` leaves that dimension open (the whole [0, 1]
    range).  A k-NN entry gives ``k``.  Coordinates are float32-exact."""
    mix = traffic["mix"]
    d = points.shape[1]
    shares = np.array([float(m["share"]) for m in mix])
    counts = np.floor(shares / shares.sum() * n).astype(int)
    counts[np.argmax(shares)] += n - counts.sum()
    which = rng.permutation(np.repeat(np.arange(len(mix)), counts))
    kind = np.zeros(n, dtype=np.int8)
    lo = np.zeros((n, d))
    hi = np.ones((n, d))
    k = np.zeros(n, dtype=np.int64)
    for i, spec in enumerate(mix):
        sel = np.flatnonzero(which == i)
        c = np.clip(_centres(spec, len(sel), traffic, points, rng), 0.0, 1.0)
        if spec["kind"] == "window":
            hw = spec["half_width"]
            if len(hw) != d:
                raise ValueError(f"half_width has {len(hw)} entries for "
                                 f"{d}-dimensional data")
            for j, h in enumerate(hw):
                if h is not None:
                    lo[sel, j] = np.clip(c[:, j] - h, 0.0, 1.0)
                    hi[sel, j] = np.clip(c[:, j] + h, 0.0, 1.0)
        elif spec["kind"] == "knn":
            kind[sel] = 1
            lo[sel] = c
            k[sel] = int(spec["k"])
        else:
            raise ValueError(f"unknown request kind {spec['kind']!r}")
    return Requests(kind, f32_exact(lo), f32_exact(hi), k)


def arrivals(traffic: dict, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due offsets in [0, seconds) of an open loop, ascending."""
    n = int(round(float(traffic["rate"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


class RequestStream:
    """Requests made on demand, in blocks, from one stream of the seed:
    the i-th request is the same whatever the loop asks for after it."""

    BLOCK = 4096

    def __init__(self, traffic: dict, points: np.ndarray,
                 rng: np.random.Generator):
        self.traffic, self.points, self.rng = traffic, points, rng
        self.blocks: list[Requests] = []

    def get(self, i: int) -> tuple[int, np.ndarray, np.ndarray, int]:
        b, j = divmod(i, self.BLOCK)
        while len(self.blocks) <= b:
            self.blocks.append(make_requests(self.traffic, self.points,
                                             self.BLOCK, self.rng))
        r = self.blocks[b]
        return int(r.kind[j]), r.lo[j], r.hi[j], int(r.k[j])

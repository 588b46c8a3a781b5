"""The plain reference, its lower-precision control, and the comparison
that decides a run's ``correct``.

The reference scans the raw points and nothing else: it imports nothing
of the program and takes nothing the program made.  A window is every
point ``p`` with ``lo <= p <= hi`` in each dimension; a k-NN answer is the
k points of least Euclidean distance.  Both are computed in float64 over
the float32-exact coordinates, so they are exact for the data the device
holds.  To stay short, a scan first narrows the points to a slab of the
first coordinate, sorted once: a window to ``[lo_0, hi_0]``, a k-NN query
to a slab that is widened until it holds the ball of its k-th distance.
The slab only skips points that cannot qualify.

The control is the same reference with every coordinate rounded to
bfloat16 and its arithmetic rounded to bfloat16 after each operation: the
precision below the float32 that the configurations state.  It exists to
be failed: the comparison must tell it from the program.

Numbers compared (each printed beside its limit):

- ``window_wrong_ids``: over the checked windows, the ids in the answer
  or the reference but not both.  Exact, limit 0.
- ``knn_gap``: over the checked k-NN answers and each rank i, the largest
  ``(d_ans[i] - d_ref[i]) / d_ref[k-1]`` of the sorted squared distances.
  Any k distinct points give ``d_ans[i] >= d_ref[i]``, so it is 0 for an
  exact answer, whichever of several points tied at the k-th distance it
  holds, and it grows with how much farther the answer's points are.  A
  malformed answer (wrong length, repeated or unknown ids) reads
  ``MALFORMED``.
- ``not_ok``: requests due in the window that did not come back ``ok``
  (refused, shed after a failed dispatch, or never answered), over all of
  them, not only the sampled.  A cell runs below its knee with room in
  the queue, so each is a lost answer.  Limit 0.

Each number's limit is set from chip readings of the program and of the
control (PERF.md); a kind of request whose number has no limit here is
refused before a run.

``knn_gap``'s limit follows from float32 rounding.  Coordinates and
queries are float32-exact, and the device sums d float32 squares of
float32 differences, so each squared distance it ranks by is within a
relative ``g = (d + 2) u / (1 - (d + 2) u)`` of the exact one (u = 2**-24:
one rounding for the difference, doubled by the square, one for the
product, at most d - 1 for the sums; every term is non-negative).  The
box mindists that certify the candidate leaves carry the same ``g``.  A
point the answer lacks therefore lies no nearer than ``(1 - g) / (1 + g)``
times any point it holds, and the i-th distance of the answer is at most
``(1 + g) / (1 - g)`` times the reference's: ``knn_gap <= 2g / (1 - g)``,
about ``2 (d + 2) u``, 8.3e-7 at d = 5, the widest configuration.  The
limit, 1e-5, is twelve times that at d = 5; the bfloat16 control reads
0.1 and more (PERF.md gives the chip readings of both).
"""
from __future__ import annotations

import numpy as np

MALFORMED = 1.0e6
# each number's limit; PERF.md gives the readings each was set from
LIMITS = {"not_ok": 0, "window_wrong_ids": 0, "knn_gap": 1e-5}
# the number that compares each kind of request
NUMBER_OF_KIND = {"window": "window_wrong_ids", "knn": "knn_gap"}


def to_bf16(a) -> np.ndarray:
    """Round to bfloat16 and back to float64 (exact)."""
    import ml_dtypes

    return np.asarray(a, dtype=np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class BruteForce:
    """Scans of the raw ``(n, d)`` points, narrowed by a sorted first
    coordinate."""

    def __init__(self, points: np.ndarray):
        self.points = np.asarray(points, dtype=np.float64)
        self.order = np.argsort(self.points[:, 0], kind="stable")
        self.sorted = np.ascontiguousarray(self.points[self.order])

    def _slab(self, a: float, b: float) -> slice:
        """The rows of ``sorted`` whose first coordinate is in [a, b]."""
        x = self.sorted[:, 0]
        return slice(int(np.searchsorted(x, a, side="left")),
                     int(np.searchsorted(x, b, side="right")))

    def window(self, lo, hi) -> np.ndarray:
        s = self._slab(lo[0], hi[0])
        p = self.sorted[s]
        inside = np.all((p >= lo) & (p <= hi), axis=1)
        return np.sort(self.order[s][inside])

    def knn_dist2(self, q, k: int) -> np.ndarray:
        """The k least squared distances from ``q``, ascending."""
        n = len(self.points)
        k = min(k, n)
        w = 1e-3
        while True:
            s = self._slab(q[0] - w, q[0] + w)
            m = s.stop - s.start
            if m >= k:
                d2 = np.sum((self.sorted[s] - q) ** 2, axis=1)
                kth = np.partition(d2, k - 1)[k - 1]
                if np.sqrt(kth) <= w or m == n:
                    return np.sort(d2)[:k]
                w = float(np.sqrt(kth)) * (1 + 1e-9)
            else:
                w *= 4

    # -- the control: the same scans in bfloat16 --------------------------
    def window_bf16(self, lo, hi) -> np.ndarray:
        lo_b, hi_b = to_bf16(lo), to_bf16(hi)
        pad = 1e-2  # bf16 moves a coordinate in [0, 1] by under 2**-9
        s = self._slab(lo_b[0] - pad, hi_b[0] + pad)
        p = to_bf16(self.sorted[s])
        inside = np.all((p >= lo_b) & (p <= hi_b), axis=1)
        return np.sort(self.order[s][inside])

    def knn_bf16(self, q, k: int) -> np.ndarray:
        """Ids of the k least bfloat16 distances (ties by id).

        Only points in a box around ``q`` are ranked.  Rounding to
        bfloat16 moves a coordinate in [0, 1] by at most 2**-9 and each
        operation by a relative 2**-9, so for d <= 5 no point farther
        than ``1.02 r + 0.02`` from ``q`` (r the exact k-th distance) can
        come before the exact k nearest; the box is that wide in each
        dimension."""
        r = float(np.sqrt(self.knn_dist2(q, k)[-1]))
        w = 1.02 * r + 0.02
        s = self._slab(q[0] - w, q[0] + w)
        near = np.all(np.abs(self.sorted[s] - q) <= w, axis=1)
        cand = self.order[s][near]
        p, qb = to_bf16(self.sorted[s][near]), to_bf16(q)
        acc = np.zeros(len(cand))
        for j in range(p.shape[1]):
            diff = to_bf16(p[:, j] - qb[j])
            acc = to_bf16(acc + to_bf16(diff * diff))
        pick = np.lexsort((cand, acc))[:k]
        return cand[pick]


def window_wrong_ids(got, want) -> int:
    got = np.asarray(got, dtype=np.int64)
    return int(len(np.setxor1d(got, want))
               + (len(got) - len(np.unique(got))))


def knn_gap(points: np.ndarray, q, got, ref_d2: np.ndarray) -> float:
    got = np.asarray(got, dtype=np.int64)
    k = len(ref_d2)
    if (len(got) != k or len(np.unique(got)) != k
            or got.min(initial=0) < 0 or got.max(initial=0) >= len(points)):
        return MALFORMED
    d_ans = np.sort(np.sum((points[got] - q) ** 2, axis=1))
    scale = ref_d2[-1] if ref_d2[-1] > 0 else 1.0
    return float(np.max(d_ans - ref_d2) / scale)


def compare(ref: BruteForce, checked: list, not_ok: int, limits: dict,
            control: bool = False) -> dict:
    """The numbers of one run against their limits.

    ``checked`` holds ``(kind, lo, hi, k, ids)`` for every sampled answer;
    ``not_ok`` counts the window's requests that came back without one.
    With ``control`` the program's answers are replaced by the bfloat16
    control's."""
    wrong, gap = 0, 0.0
    n_win = n_knn = 0
    for kind, lo, hi, k, ids in checked:
        if kind == 0:
            n_win += 1
            got = ref.window_bf16(lo, hi) if control else ids
            wrong += window_wrong_ids(got, ref.window(lo, hi))
        else:
            n_knn += 1
            got = ref.knn_bf16(lo, k) if control else ids
            gap = max(gap, knn_gap(ref.points, lo, got, ref.knn_dist2(lo, k)))
    out = {"not_ok": {"value": not_ok, "limit": limits["not_ok"]}}
    if n_win:
        out["window_wrong_ids"] = {"value": wrong,
                                   "limit": limits["window_wrong_ids"]}
    if n_knn:
        out["knn_gap"] = {"value": gap, "limit": limits["knn_gap"]}
    return out


def passes(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())

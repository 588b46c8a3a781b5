"""The benchmark's data: each deployment's point set.

``osm_like`` and ``nycyt_like`` are copied from the program's
``repro/core/datasets.py`` so that the yardstick cannot move with the
program.  A configuration fixes its data with ``data_seed``; the run's
``--seed`` draws the requests and their arrivals.  The data does not vary
with ``--seed`` because the served table's shapes follow it: FMBI's leaf
count is a property of the exact points, every compiled program of the
served path is keyed by those shapes, and a run on other points would
compile every program again (about 9 s each on a v5e) inside set-up.

Coordinates are rounded to float32, the precision the device table
stores, and kept as float64: the reference and the device then compare
equal values.
"""
from __future__ import annotations

import numpy as np


def f32_exact(a) -> np.ndarray:
    return np.asarray(a, dtype=np.float32).astype(np.float64)


def osm_like(n: int, seed: int = 0) -> np.ndarray:
    """2-D: dense city clusters + sparse countryside + empty oceans."""
    rng = np.random.default_rng(seed)
    n_clusters = 64
    centers = rng.random((n_clusters, 2))
    # keep clusters on "land": reject centers in two ocean bands
    ocean = (centers[:, 0] < 0.18) | (
        (centers[:, 0] > 0.42) & (centers[:, 0] < 0.55)
    )
    centers[ocean, 0] = rng.random(ocean.sum()) * 0.25 + 0.6
    weights = rng.pareto(1.2, n_clusters) + 0.05
    weights /= weights.sum()
    n_cluster_pts = int(n * 0.85)
    counts = rng.multinomial(n_cluster_pts, weights)
    parts = []
    for c, k in zip(centers, counts):
        if k == 0:
            continue
        scale = rng.uniform(0.002, 0.03)
        parts.append(rng.normal(c, scale, size=(k, 2)))
    sprinkle = rng.random((n - n_cluster_pts, 2))
    sprinkle[:, 0] = sprinkle[:, 0] * 0.4 + 0.55  # countryside strip
    parts.append(sprinkle)
    pts = np.concatenate(parts)[:n]
    pts = np.clip(pts, 0.0, 1.0)
    return pts[np.random.default_rng(seed + 1).permutation(len(pts))].astype(
        np.float64
    )


def nycyt_like(n: int, d: int = 5, seed: int = 0) -> np.ndarray:
    """5-D correlated trips: (pickup_x, pickup_y, dropoff_x, dropoff_y, t).

    Pickups cluster around hotspots; dropoffs correlate with pickups (short
    trips dominate); time has rush-hour peaks.  ``d < 5`` selects the first
    d dimensions (paper Figure 9 protocol).
    """
    rng = np.random.default_rng(seed)
    hotspots = rng.random((12, 2)) * 0.6 + 0.2
    w = rng.pareto(1.5, 12) + 0.1
    w /= w.sum()
    which = rng.choice(12, size=n, p=w)
    pickup = hotspots[which] + rng.normal(0, 0.04, size=(n, 2))
    trip = rng.exponential(0.08, size=(n, 1)) * rng.normal(
        0, 1.0, size=(n, 2)
    )
    dropoff = pickup + trip
    peaks = np.array([0.35, 0.75])
    t = (
        peaks[rng.integers(0, 2, n)] + rng.normal(0, 0.1, n)
    ).reshape(n, 1)
    pts = np.concatenate([pickup, dropoff, t], axis=1)
    pts = np.clip(pts, 0.0, 1.0)
    return pts[:, :d].astype(np.float64)


def make_points(config: dict) -> np.ndarray:
    """The configuration's point set, float32-exact."""
    kind, n, seed = config["generator"], int(config["n_points"]), int(
        config["data_seed"])
    if kind == "osm_like":
        return f32_exact(osm_like(n, seed))
    if kind == "nycyt_like":
        return f32_exact(nycyt_like(n, int(config["dim"]), seed))
    raise ValueError(f"unknown generator {kind!r}")

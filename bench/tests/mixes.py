"""Mixes the harness supports but no cell runs yet, as the tests drive
them: k-NN, a 5-D mix of windows and k-NN, and a closed loop.  A cell that
sends them adds its own traffic file with its measured rate."""
import copy

from bench import generator as gen

KNN_NEAR = {
    "loop": "open", "rate": 40.0, "queue_bound": 100000,
    "mix": [{"kind": "knn", "share": 1.0, "k": 16,
             "centre": {"from": "data", "jitter": 0.001}}],
    "check": 256, "warmup_seconds": 0.3, "warmup_passes": 1,
}
MIXED = {
    "loop": "open", "rate": 40.0, "queue_bound": 100000,
    "mix": [{"kind": "window", "share": 0.75, "centre": {"from": "data"},
             "half_width": [0.01, 0.01, None, None, 0.05]},
            {"kind": "knn", "share": 0.25, "k": 16,
             "centre": {"from": "data"}}],
    "check": 256, "warmup_seconds": 0.3, "warmup_passes": 1,
}


def closed(traffic: dict, outstanding: int = 8) -> dict:
    t = {k: v for k, v in copy.deepcopy(traffic).items() if k != "rate"}
    return dict(t, loop="closed", outstanding=outstanding)


def window_focused() -> dict:
    return dict(gen.load_traffic("window-focused"), rate=40.0,
                warmup_seconds=0.3, warmup_passes=1)


# the limit a k-NN test run is held to (no chip reading sets one yet)
KNN_LIMITS = {"knn_gap": 1e-3}

"""The mixes as the tests drive them on the CPU: the cells' own traffic
files, shortened, and mixes that no cell runs yet (a 5-D mix of windows
and k-NN, a closed loop, and a hot spot that drifts).  A cell that sends
them adds its own traffic file with its measured rate."""
import copy

from bench import generator as gen

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


def shortened(name: str) -> dict:
    """A cell's traffic file at 40 requests/s, with a short warm-up."""
    return dict(gen.load_traffic(name), rate=40.0, warmup_seconds=0.3,
                warmup_passes=1)


def window_focused() -> dict:
    return shortened("window-focused")


def knn_near() -> dict:
    return shortened("knn-near")


def drifting(traffic: dict, every_s: float) -> dict:
    """The mix with its focused windows' hot spot jumping to a data point
    every ``every_s`` seconds."""
    t = copy.deepcopy(traffic)
    for m in t["mix"]:
        if m["centre"]["from"] == "focus":
            m["centre"]["drift"] = {"every_s": every_s, "to": "data"}
    return t

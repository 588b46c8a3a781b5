"""The trace reduction: busy union, idle share and its labels, module and
kernel time, on a hand-made trace and on a small one recorded on a v5e."""
import gzip
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "trace_v5e.json.gz"


def hand_trace():
    ms = 1_000_000
    ops = [["%while.1 = (s32[]) while(...)", 10 * ms, 5 * ms, None],
           ["%fusion.2 = s32[8] fusion(...)", 11 * ms, 2 * ms, None],  # in it
           ["%fusion.3 = s32[8] fusion(...)", 12 * ms, 6 * ms, None],  # overlaps
           ["%pair_window_ids.3 = s32[8,341] custom-call(...)", 30 * ms,
            10 * ms, None],
           ["%copy.3 = s32[8] copy(...)", 95 * ms, 10 * ms, None]]  # past end
    modules = [["jit__frontier_count(7)", 10 * ms, 8 * ms, None],
               ["jit__fused_pack_scan(9)", 30 * ms, 10 * ms, None],
               ["jit__fused_id_pack(3)", 95 * ms, 10 * ms, None]]
    host = [["bench.window", 0, 100 * ms, None],
            ["engine.window", 5 * ms, 40 * ms, None],
            ["generator.submit", 50 * ms, 1 * ms, None],
            ["other.span", 60 * ms, 10 * ms, None]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_hand_trace():
    s = tr.reduce(hand_trace())
    assert s["window_s"] == pytest.approx(0.100)
    # busy: [10, 18] + [30, 40] + [95, 100] clipped = 23 ms
    assert s["busy_s"] == pytest.approx(0.023)
    assert s["modules"]["_frontier_count"] == pytest.approx((0.008, 1))
    assert s["modules"]["_fused_id_pack"][0] == pytest.approx(0.005)
    assert s["ops"]["_fused_pack_scan:pair_window_ids.3"] == pytest.approx(
        (0.010, 1, 0.010))
    # a while loop's own time excludes the op nested in its body
    assert s["ops"]["_frontier_count:while.1"] == pytest.approx(
        (0.005, 1, 0.003))
    # idle 77 ms, split by what the host did: engine.window covers
    # [5, 10], [18, 30] and [40, 45]; generator.submit [50, 51]
    idle = s["idle"]
    assert sum(idle.values()) == pytest.approx(0.077)
    assert idle["host:engine.window"] == pytest.approx(0.022)
    assert idle["host:generator.submit"] == pytest.approx(0.001)
    assert idle["host:none"] == pytest.approx(0.054)
    b = tr.breakdown(s)
    assert b["device_ops"][0] == ["_fused_pack_scan:pair_window_ids.3",
                                  pytest.approx(0.010)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_idle_without_an_engine_call_is_the_host_waiting():
    t = hand_trace()
    t["planes"][1]["lines"][0]["events"] = [["bench.window", 0, 100_000_000,
                                             None]]
    assert set(tr.reduce(t)["idle"]) == {"host:none"}


def test_recorded_v5e_trace():
    """A 60 ms slice of a traced window of focused windows over 1e7 OSM-like
    points on one v5e, checked against sums taken here without the reduction."""
    trace = json.load(gzip.open(FIXTURE, "rt"))
    s = tr.reduce(trace)
    dev = {ln["name"]: ln["events"] for p in trace["planes"]
           if p["name"].startswith("/device:") for ln in p["lines"]}
    (_, t0, d, _), = [e for p in trace["planes"] if p["name"] == "/host:CPU"
                      for ln in p["lines"] for e in ln["events"]
                      if e[0] == "bench.window"]
    t1 = t0 + d
    # busy: a 1 us grid over the window, marked where any op runs
    grid = [False] * ((t1 - t0) // 1000 + 1)
    for _, st, du, _ in dev["XLA Ops"]:
        a, b = max(st, t0), min(st + du, t1)
        for i in range((a - t0) // 1000, (b - t0) // 1000):
            grid[i] = True
    assert s["busy_s"] == pytest.approx(sum(grid) * 1e-6, abs=2e-5)
    idle = 1 - s["busy_s"] / s["window_s"]
    assert 0 < idle < 1
    assert sum(s["idle"].values()) == pytest.approx(s["window_s"] - s["busy_s"])
    # module time: the module events clipped to the window, by name
    want = {}
    for name, st, du, _ in dev["XLA Modules"]:
        a, b = max(st, t0), min(st + du, t1)
        if b > a:
            k = tr.module_key(name)
            want[k] = want.get(k, 0) + (b - a) * 1e-9
    assert {k: v[0] for k, v in s["modules"].items()} == pytest.approx(want)
    assert "_fused_pack_scan" in want
    # kernel time: the pair scan kernel's custom calls
    kern = sum((min(st + du, t1) - max(st, t0)) * 1e-9
               for name, st, du, _ in dev["XLA Ops"]
               if name.startswith("%pair_window_ids."))
    got = sum(v[0] for k, v in s["ops"].items()
              if k.split(":")[1].startswith("pair_window_ids."))
    assert kern > 0 and got == pytest.approx(kern)


def test_module_key():
    assert tr.module_key("jit__fused_pack_scan(123)") == "_fused_pack_scan"
    assert tr.module_key("jit_foo") == "foo"


def test_needs_the_window_span_and_a_device():
    t = hand_trace()
    with pytest.raises(ValueError):
        tr.reduce({"planes": [t["planes"][0]]})
    with pytest.raises(ValueError):
        tr.reduce({"planes": [t["planes"][1]]})

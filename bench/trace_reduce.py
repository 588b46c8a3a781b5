"""From a profiler trace to the numbers the per-layer metrics read.

A trace is first cut down to a plain structure that JSON can hold, so a
small recorded one can be kept as a test fixture:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, module], ...]}]}]}

``module`` is the event's ``hlo_module`` (or null).  Device planes keep
their ``XLA Ops`` and ``XLA Modules`` lines; host planes keep only the
benchmark's own spans (names in :data:`SPAN_PREFIXES`).

:func:`reduce` then measures, inside the ``bench.window`` span:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices traced; ``window_s``: the span;
- device seconds and counts per module (``XLA Modules``), and per op
  (``XLA Ops``, keyed ``module:op`` by the module that holds it; a Pallas
  kernel is a custom call named after its kernel, ``pair_window_ids.3``);
- the idle time between device operations, split by what the host was
  doing: inside an engine call (``host:engine.window``, ``host:engine.knn``:
  host work of the server), sending a request, or none of these
  (``host:none``: the server waited for requests).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIXES = ("bench.", "engine.", "generator.", "build.")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# the spans an idle gap may be laid to, in the order they are preferred
GAP_LABELS = ("engine.window", "engine.knn", "generator.submit")


def from_profile_dir(log_dir: str) -> dict:
    """Read the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir`` into the plain structure."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {log_dir}, "
                           f"found {len(paths)}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    planes = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIXES):
                    continue
                module = None
                if device:
                    module = dict(ev.stats).get("hlo_module")
                events.append([ev.name, int(ev.start_ns), int(ev.duration_ns),
                               module])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, t0, t1):
    for name, s, d, mod in events:
        e = s + d
        if e <= t0 or s >= t1:
            continue
        yield name, max(s, t0), min(e, t1), mod


def module_key(name: str) -> str:
    """``jit__fused_pack_scan(123)`` -> ``_fused_pack_scan``: the jitted
    function's name, as the program's source spells it."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def op_key(module: str, name: str) -> str:
    """``_fused_pack_scan:copy.9`` from the module and an HLO text such as
    ``%copy.9 = f32[...] copy(...)``."""
    return f"{module}:{name.split(' = ')[0].lstrip('%')}"


def _self_times(events):
    """Each event's duration less the events nested inside it (an XLA
    ``while`` holds the ops of its body)."""
    out, stack = [], []
    for i, (name, s, e, mod) in enumerate(sorted(events,
                                                 key=lambda x: (x[1], -x[2]))):
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append([name, s, e, e - s])
        if stack and e <= stack[-1][1]:
            out[stack[-1][0]][3] -= e - s
        stack.append((i, e))
    return out


def reduce(trace: dict) -> dict:
    host_spans = defaultdict(list)
    device_lines = defaultdict(dict)
    for plane in trace["planes"]:
        for line in plane["lines"]:
            if DEVICE_PLANE.match(plane["name"]):
                device_lines[plane["name"]][line["name"]] = line["events"]
            else:
                for name, s, d, _ in line["events"]:
                    host_spans[name].append((s, s + d))
    windows = host_spans.get(WINDOW_SPAN)
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    t0, t1 = min(s for s, _ in windows), max(e for _, e in windows)
    if not device_lines:
        raise ValueError("the trace holds no device plane")

    busy, gaps = [], defaultdict(int)
    ops = defaultdict(lambda: [0, 0, 0])
    modules = defaultdict(lambda: [0, 0])
    label_spans = {}
    for k in GAP_LABELS:
        spans = _merge(host_spans.get(k, []))
        label_spans[k] = (spans, [e for _, e in spans])
    for lines in device_lines.values():
        mods = sorted((s, e, module_key(name)) for name, s, e, _ in
                      _clip(lines.get(MODULES_LINE, []), t0, t1))
        for s, e, key in mods:
            modules[key][0] += e - s
            modules[key][1] += 1
        starts = [m[0] for m in mods]
        iv = []
        for name, s, e, total in _self_times(
                list(_clip(lines.get(OPS_LINE, []), t0, t1))):
            iv.append((s, e))
            j = bisect.bisect_right(starts, s) - 1
            mod = mods[j][2] if j >= 0 and mods[j][1] >= e else "?"
            key = op_key(mod, name)
            ops[key][0] += e - s
            ops[key][1] += 1
            ops[key][2] += total
        merged = _merge(iv)
        busy.append(sum(e - s for s, e in merged))
        edges = [t0] + [x for se in merged for x in se] + [t1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge > gs:
                for label, ns in _gap_labels(gs, ge, label_spans).items():
                    gaps[label] += ns
    n_dev = len(device_lines)
    ns = 1e-9
    return {
        "window_s": (t1 - t0) * ns,
        "busy_s": sum(busy) / n_dev * ns,
        "devices": n_dev,
        # per op: (device seconds, count, self seconds)
        "ops": {k: (v[0] / n_dev * ns, v[1] // n_dev, v[2] / n_dev * ns)
                for k, v in ops.items()},
        "modules": {k: (v[0] / n_dev * ns, v[1] // n_dev)
                    for k, v in modules.items()},
        "idle": {k: v / n_dev * ns for k, v in gaps.items()},
    }


def _cover(spans, ends, gs: int, ge: int) -> int:
    cover = 0
    for i in range(bisect.bisect_right(ends, gs), len(spans)):
        s, e = spans[i]
        if s >= ge:
            break
        cover += min(e, ge) - max(s, gs)
    return cover


def _gap_labels(gs: int, ge: int, label_spans: dict) -> dict:
    """Split one idle gap by what the host was doing: the part inside each
    labelled span, in the order of ``GAP_LABELS``, and the rest as
    ``host:none``."""
    out, left = {}, ge - gs
    for name, (spans, ends) in label_spans.items():
        c = min(_cover(spans, ends, gs, ge), left)
        if c > 0:
            out[f"host:{name}"] = c
            left -= c
    if left > 0:
        out["host:none"] = left
    return out


def breakdown(summary: dict, top: int = 10) -> dict:
    """The device operations that took most time (self time: a ``while``
    less the ops of its body), named ``module:op``, and the idle time by
    what the host was doing, as ``[[name, seconds], ...]``."""
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1][2])[:top]
    idle = sorted(summary["idle"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v[2]] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}

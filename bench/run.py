#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler and prints its per-layer metrics, the
device's busy and window seconds, and a breakdown.  Either way the run
compares the answers it sampled with the plain reference and prints each
number compared beside its limit, as the last lines of standard error and
under ``checks``, the last key of the result.  The result is the last line
of standard output, one JSON object.

Without a TPU (or with fewer chips than the cell asks for, or with
interpret-mode kernels) the run exits 2 before any measurement and prints
no result.  ``--control 1`` also reads the bfloat16 control on the same
sampled requests (see ``bench/reference.py``); the benchmark's own runs do
not ask for it.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import generator as gen  # noqa: E402
from bench import harness as h  # noqa: E402
from bench import reference, trace_reduce  # noqa: E402
from bench.datasets import make_points  # noqa: E402

def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(cell: h.Cell, seed: int, seconds: float, trace: bool,
             devices, control: bool = False, t_start: float = T_START,
             limits: dict = reference.LIMITS) -> tuple[dict, dict]:
    """One run.  Returns the result line's object (without ``checks``) and
    the numbers compared, each beside its limit."""
    import jax

    clock = h.CompileClock().__enter__()
    try:
        points = make_points(cell.config)
        server, build = h.build_and_boot(cell.config, points)
        say(f"[build] points={len(points)} dim={points.shape[1]} "
            f"leaves={server.dev.n_leaves} leaf_size={server.dev.leaf_size} "
            f"bulk_load_s={build['bulk_load_s']} upload_s={build['upload_s']}")
        warm = h.warm_up(server, cell.traffic, points, seed, clock,
                         float(cell.traffic["warmup_seconds"]), cell.config)
        setup = clock.snapshot()
        say(f"[setup] planned_batches={warm['planned_batches']} "
            f"planned_builds={warm['planned_builds']} "
            f"warmup_passes={warm['passes']} "
            f"pass_builds={warm['pass_builds']} "
            f"builds={setup['builds']} build_s={setup['build_s']} "
            f"cache_hits={setup['cache_hits']} "
            f"cache_misses={setup['cache_misses']}")
        say(f"[setup] builds_by_function {clock.by_function_text()}")
        setup_by_function = clock.by_function_copy()

        timed = h.TimedServer(server)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        setup_s = time.monotonic() - t_start
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        stats_start = h.server_stats(server)
        try:
            rec, fe_stats, window = h.run_window(
                timed, cell.traffic, points, seconds,
                gen.rng_for(seed, gen.STREAM_WINDOW, 0),
                gen.rng_for(seed, gen.STREAM_WINDOW, 1),
                h.sample_for(cell.traffic, seed),
                rng_drift=gen.rng_for(seed, gen.STREAM_WINDOW, 2))
        finally:
            if trace:
                jax.profiler.stop_trace()
        stats_end = h.server_stats(server)
        window_builds = clock.builds - setup["builds"]
        window_cache = (clock.hits - setup["cache_hits"],
                        clock.misses - setup["cache_misses"])
    finally:
        clock.__exit__()

    mem = devices[0].memory_stats() or {}
    summary = None
    if trace:
        summary = trace_reduce.reduce(trace_reduce.from_profile_dir(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    leaf_lo = np.asarray(server.dev.leaf_lo)[: server.dev.n_leaves]
    leaf_hi = np.asarray(server.dev.leaf_hi)[: server.dev.n_leaves]
    leaf_size = int(server.dev.leaf_size)
    calls = timed.calls
    del server, timed
    e2e, attempted, failed = h.end_to_end(rec, window, seconds, setup_s, build)
    say(f"[window] attempted={attempted} failed={failed} "
        f"batches={fe_stats.batches} completed={fe_stats.completed} "
        f"builds_in_window={window_builds} "
        f"cache_hits={window_cache[0]} cache_misses={window_cache[1]} "
        f"{clock.by_function_text(setup_by_function)}")
    say("[window] slowest " + h.slowest_text(rec, window))
    say("[window] server_counters " + " ".join(
        f"{k}:{stats_start[k]}->{v}" for k, v in stats_end.items()
        if v or stats_start[k]))

    # the reference runs once the window has closed, the peak has been
    # read and the server is gone
    t_ref = time.monotonic()
    ref = reference.BruteForce(points)
    checked = h.checked_requests(rec)
    not_ok = rec.not_ok(window[1])
    numbers = reference.compare(ref, checked, not_ok, limits)
    say(f"[reference] checked={len(checked)} "
        f"seconds={time.monotonic() - t_ref}")
    if control:
        ctl = reference.compare(ref, checked, not_ok, limits, control=True)
        say("[control] " + " ".join(f"{k}={v['value']}" for k, v in ctl.items()))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result = {"correct": reference.passes(numbers), "attempted": attempted,
              "failed": failed}
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        ctx = h.Context(cell, seconds, rec, window, fe_stats, calls, build,
                        window_builds, summary, leaf_lo, leaf_hi, leaf_size,
                        devices[0].device_kind, ref, stats_start, stats_end)
        result["metrics"] = {}
        for m in cell.per_layer:
            value = h.load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    result["device"] = device
    if trace:
        result["breakdown"] = trace_reduce.breakdown(summary)
    if control:
        result["control"] = ctl
    return result, numbers


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = h.resolve_cell(h.load_manifest(ROOT), args.workload, ROOT)
        devices = h.device_gate(cell.chips)
    except h.SetupError as e:
        say(f"bench/run.py: {e}")
        return 2
    say(f"[device] platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)} "
        f"cache={h.enable_compile_cache(ROOT)}")
    result, numbers = run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), devices,
                               control=bool(args.control))
    result["checks"] = numbers
    for name, v in numbers.items():
        say(f"check {name} {v['value']} limit {v['limit']}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: the highest offered rate at
which the backlog does not grow over the window.

    python3 bench/sweep.py --workload <cell> --seed <n> \\
        --rates 20,30,40,50 [--requests 1000]

One process: set-up as ``bench/run.py`` makes it (data, boot, warm-up),
then one window per rate, ascending, each at that rate and otherwise as
the traffic file says, and long enough to offer ``--requests`` requests.
A rate is judged only on a window in which no program was built; a window
that built one is run again.  Where the boot changes its server as it
serves, each window is served by a server freshly booted alike, as a
cell's window is, and is judged as it comes, with the programs it built:
its table's shapes grow with what it grafts, so a second window would
build again.  A rate is sustained when

- every request is answered ``ok``;
- at least 98% of the requests due in the window were answered inside
  it: a backlog that grows leaves the later ones waiting past its end;
- the median latency of the requests due in the last third of the window
  is at most 1.5x that of the first third plus 10 ms: a growing backlog
  makes every later request wait longer than the one before it.

The sweep stops after two rates in a row that are not sustained.  Each
window prints one JSON line; the last line names the knee.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import generator as gen  # noqa: E402
from bench import harness as h  # noqa: E402
from bench.datasets import make_points  # noqa: E402


def judge(rec: h.Records, window, seconds: float) -> dict:
    t0, t_end = window
    due = np.asarray(rec.due)
    done = np.asarray(rec.done, dtype=np.float64)
    ok = np.asarray(rec.ok, dtype=bool)
    lat = np.where(ok, done - due, np.inf)
    third = seconds / 3
    first = lat[due < t0 + third]
    last = lat[due >= t_end - third]
    p50_first = float(np.median(first)) if len(first) else np.inf
    p50_last = float(np.median(last)) if len(last) else np.inf
    late = np.asarray(rec.sent) - due
    answered = int((ok & (done <= t_end)).sum())
    return {
        "offered": len(due),
        "failed": int((~ok).sum()),
        "answered_in_window": answered,
        "p50_ms": h.percentile_ms(lat, 50),
        "p99_ms": h.percentile_ms(lat, 99),
        "p50_first_third_ms": p50_first * 1e3,
        "p50_last_third_ms": p50_last * 1e3,
        "late_p99_ms": h.percentile_ms(late, 99),
        "sustained": bool(ok.all() and answered >= 0.98 * len(due)
                          and p50_last <= 1.5 * p50_first + 0.010),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=1000,
                    help="requests each rate's window offers")
    ap.add_argument("--rates", required=True,
                    help="offered requests per second, comma-separated")
    args = ap.parse_args(argv)
    try:
        cell = h.resolve_cell(h.load_manifest(ROOT), args.workload, ROOT)
        devices = h.device_gate(cell.chips)
    except h.SetupError as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 2
    if cell.traffic["loop"] != "open":
        print("bench/sweep.py: a closed loop has no offered rate",
              file=sys.stderr)
        return 2
    h.enable_compile_cache(ROOT)
    adaptive = h.load_boot(h.boot_name(cell.config)).CHANGES_AS_IT_SERVES
    with h.CompileClock() as clock:
        points = make_points(cell.config)
        server, build = h.build_and_boot(cell.config, points)
        h.warm_up(server, cell.traffic, points, args.seed, clock,
                  float(cell.traffic["warmup_seconds"]), cell.config)
        print(json.dumps({"cell": cell.name, "build": build,
                          "setup_s": time.monotonic() - T_START,
                          "device": devices[0].device_kind}), flush=True)
        knee, misses = None, 0
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate=rate)
            seconds = args.requests / rate
            for attempt in range(2):
                if adaptive:
                    server, _ = h.build_and_boot(cell.config, points)
                builds, counters = clock.builds, h.server_stats(server)
                rec, stats, window = h.run_window(
                    server, traffic, points, seconds,
                    gen.rng_for(args.seed, gen.STREAM_WINDOW, 0, i, attempt),
                    gen.rng_for(args.seed, gen.STREAM_WINDOW, 1, i, attempt),
                    rng_drift=gen.rng_for(args.seed, gen.STREAM_WINDOW, 2, i,
                                          attempt))
                row = {"rate": rate, "seconds": seconds,
                       **judge(rec, window, seconds),
                       "batches": stats.batches,
                       "batch_mean": stats.completed / max(stats.batches, 1),
                       "builds": clock.builds - builds,
                       "server": {k: v - counters[k] for k, v in
                                  h.server_stats(server).items()}}
                print(json.dumps(row), flush=True)
                if adaptive or not row["builds"]:
                    break
            if row["builds"] and not adaptive:
                row["sustained"] = False  # no window of this rate without a build
            if row["sustained"]:
                knee, misses = rate, 0
            else:
                misses += 1
                if misses == 2:
                    break
    print(json.dumps({"cell": cell.name, "knee": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

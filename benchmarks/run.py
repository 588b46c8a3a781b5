"""Benchmark orchestrator: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced sizes
  PYTHONPATH=src python -m benchmarks.run --only fig8
"""
from __future__ import annotations

import argparse
import time

from repro import compile_cache

from . import (
    bench_adaptive,
    bench_construction,
    bench_dims,
    bench_leafstats,
    bench_parallel,
    bench_queries,
)

SUITES = {
    "table1": lambda q: bench_leafstats.run(n=120_000 if q else 2_000_000),
    "fig7_build": lambda q: bench_construction.run(n=120_000 if q else 2_000_000),
    "fig7_query": lambda q: bench_queries.run(n=120_000 if q else 1_000_000),
    "fig8": lambda q: bench_adaptive.run(n=100_000 if q else 600_000),
    "fig9": lambda q: bench_dims.run(n=60_000 if q else 400_000),
    "fig11": lambda q: bench_parallel.run(n=60_000 if q else 400_000),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", choices=sorted(SUITES))
    args = ap.parse_args(argv)
    compile_cache.enable()
    todo = {args.only: SUITES[args.only]} if args.only else SUITES
    t0 = time.time()
    for name, fn in todo.items():
        t1 = time.time()
        print(f"\n######## {name} ########")
        fn(args.quick)
        print(f"[{name}: {time.time()-t1:.1f}s]")
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s; "
          f"tables under experiments/")


if __name__ == "__main__":
    main()

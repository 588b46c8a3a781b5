"""The benchmark: one cell of BENCHMARK.json run once by ``bench/run.py``."""

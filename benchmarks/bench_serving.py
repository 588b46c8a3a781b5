"""Open-loop serving benchmark: the async frontend under offered load.

Drives :class:`repro.serve.frontend.Frontend` (real clock, dispatcher
thread) with a fixed-rate open-loop generator — arrivals at ``i / rate``
regardless of completions, the honest way to measure a bounded queue:
a closed-loop client self-throttles and can never expose shedding.

Three workload mixes (75% window / 25% k-NN):

  * ``hotspot`` — queries concentrated in an 8% hot cube (the adaptive
    engine's favorite case, and the batch former's: one lane fills fast),
  * ``uniform`` — uniform small windows across the space,
  * ``adversarial`` — fat windows (large result sets), degenerate
    point-thin windows, and far-corner k-NN in one stream, defeating
    both the router's pruning and any single pow2 padding bucket.

Each mix runs at sub- (0.5x), at- (1.0x), and over- (2x) the measured
capacity (a warm ``batch_max`` dispatch timed directly), recording p50 /
p99 latency, achieved throughput, shed + rejection rate, and peak queue
depth into ``BENCH_SERVE.json``.  A separate full-throttle **burst** run
guarantees saturation regardless of machine speed and asserts the
robustness contract: queue depth never exceeds the bound, excess load is
rejected/shed *with certificates* rather than queued without bound, and
every admitted answer is id-identical to the same server queried
offline.

  PYTHONPATH=src python -m benchmarks.bench_serving           # full, writes BENCH_SERVE.json
  PYTHONPATH=src python -m benchmarks.bench_serving --smoke   # CI gate, no write

``--smoke`` runs reduced scale and fails (exit 1) when the structural
contract breaks or when a gated latency/throughput key regresses >30%
(plus a noise floor) against the ``smoke_*`` baselines committed in
BENCH_SERVE.json by the last full run.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import zlib

import numpy as np

from repro import compile_cache
from repro.core import PageStore, bulk_load
from repro.core.datasets import osm_like
from repro.core.ioutil import atomic_write_json
from repro.serve.engine import DeviceQueryServer
from repro.serve.frontend import Frontend

from .common import buffer_pages

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_SERVE = ROOT / "BENCH_SERVE.json"

SMOKE_N = 60_000
FULL_N = 600_000
K = 8

# latency keys gated against committed smoke baselines: >30% + noise floor
# fails.  Latency floors are generous — these runs share a CI box with the
# kernel jobs, and a regression that matters here is 2x, not 30ms.
SMOKE_GATED_LATENCY = {
    # floors sized from observed run-to-run spread (queueing delay near
    # capacity swings 25-55% between runs of the same build): the gate
    # catches a serialized dispatcher or lock-contention collapse (p50 in
    # seconds), not scheduler weather
    "hotspot_sub_p50_ms": 150.0,
    "hotspot_sub_p99_ms": 400.0,
    "uniform_sub_p50_ms": 150.0,
    "adversarial_sub_p50_ms": 300.0,
}
# throughput keys: regression = *lower* than baseline by >40%
SMOKE_GATED_THROUGHPUT = {
    "hotspot_at_throughput_qps",
    "uniform_at_throughput_qps",
}
SMOKE_REGRESSION_FRAC = 0.30
SMOKE_THROUGHPUT_FRAC = 0.40
# static ceilings when no baseline is committed (first run, --n override)
SMOKE_CEILING_P50_MS = 500.0
SMOKE_CEILING_P99_MS = 2500.0


def _build_server(n: int, seed: int = 0):
    pts = osm_like(n, seed=seed)
    idx = bulk_load(pts, buffer_pages(pts), PageStore(buffer_pages(pts)))
    srv = DeviceQueryServer.from_index(idx, microbatch=64)
    return pts, srv


def _mix_stream(mix: str, d: int, n: int, seed: int):
    """Deterministic request stream: list of ("window", lo, hi) and
    ("knn", q, k) tuples, 75/25, per-mix geometry."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        kind = "knn" if i % 4 == 3 else "window"
        if mix == "hotspot":
            c = rng.random(d) * 0.08 + 0.45
            half = 0.02
        elif mix == "uniform":
            c = rng.random(d) * 0.9
            half = 0.02
        else:  # adversarial: fat / degenerate / far-corner rotation
            j = i % 3
            c = rng.random(d) * 0.9
            half = (0.2, 0.0, 0.02)[j]
            if j == 2 and kind == "knn":
                c = np.full(d, 0.999)  # far corner: router prunes nothing near
        if kind == "window":
            out.append(("window", np.clip(c - half, 0, 1),
                        np.clip(c + half, 0, 1)))
        else:
            out.append(("knn", np.clip(c, 0, 1), K))
    return out


def warm_server(srv, d: int, batch_max: int = 64) -> None:
    """Compile every pow2 batch bucket for both query kinds up front —
    otherwise the first undersized microbatch of each shape stalls the
    dispatcher on a jit compile and poisons the latency percentiles."""
    rng = np.random.default_rng(3)
    b = 1
    while b <= batch_max:
        c = rng.random((b, d)) * 0.9
        srv.window(np.clip(c - 0.02, 0, 1), np.clip(c + 0.02, 0, 1))
        srv.knn(rng.random((b, d)), K)
        b *= 2


def measure_capacity(srv, d: int, *, n_requests: int = 192,
                     batch_max: int = 64) -> float:
    """End-to-end queries/second *through the frontend* (dispatcher
    thread, batching, locking, per-request bookkeeping included) — the
    raw engine number overstates what an open-loop client can actually
    push, so rates scaled from it would mislabel saturation as "sub"."""
    stream = _mix_stream("uniform", d, n_requests, seed=3)
    fe = Frontend(srv, queue_bound=n_requests + 1,
                  batch_max=batch_max, batch_window_s=0.001).start()
    t0 = time.monotonic()
    for item in stream:
        if item[0] == "window":
            fe.submit_window(item[1], item[2])
        else:
            fe.submit_knn(item[1], item[2])
    fe.stop()  # drains everything through dispatch
    elapsed = time.monotonic() - t0
    return fe.stats.completed / max(elapsed, 1e-9)


def run_open_loop(srv, stream, rate_qps: float, *,
                  queue_bound: int = 256, batch_max: int = 64,
                  batch_window_s: float = 0.002,
                  deadline_s: float | None = None,
                  brownout_high: int | None = None) -> dict:
    """Fixed-rate arrivals: request ``i`` is submitted at ``t0 + i/rate``
    whether or not earlier ones completed (open loop)."""
    fe = Frontend(
        srv, queue_bound=queue_bound, batch_max=batch_max,
        batch_window_s=batch_window_s, default_deadline_s=deadline_s,
        brownout_high=brownout_high,
        brownout_low=None if brownout_high is None else brownout_high // 4,
        brownout_knn_rounds=1,
    ).start()
    reqs = []
    t0 = time.monotonic()
    for i, item in enumerate(stream):
        target = t0 + i / rate_qps
        now = time.monotonic()
        if target > now:
            time.sleep(target - now)
        if item[0] == "window":
            reqs.append(fe.submit_window(item[1], item[2]))
        else:
            reqs.append(fe.submit_knn(item[1], item[2]))
    t_submit_end = time.monotonic()
    fe.stop()  # drains the queue through dispatch
    t_end = time.monotonic()

    lat = np.array([r.latency for r in reqs if r.status == "ok"])
    n = len(reqs)
    st = fe.stats
    out = {
        "offered_qps": round(n / max(t_submit_end - t0, 1e-9), 1),
        "throughput_qps": round(st.completed / max(t_end - t0, 1e-9), 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3)
        if lat.size else -1.0,
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3)
        if lat.size else -1.0,
        "shed_rate": round(st.dropped / max(n, 1), 4),
        "rejected": st.rejected,
        "timed_out": st.timed_out,
        "shed": st.shed,
        "depth_peak": st.depth_peak,
        "brownout_batches": st.brownout_batches,
        "batches": st.batches,
    }
    return out, reqs, fe


def saturation_burst(srv, pts, *, queue_bound: int = 64,
                     n_requests: int = 256, seed: int = 9) -> dict:
    """Full-throttle burst (no pacing): saturation is guaranteed on any
    machine, so the structural robustness contract is checkable in CI:

      * peak queue depth never exceeds the bound,
      * the excess is rejected/shed — nonzero, and every dropped request
        carries a completeness certificate,
      * every admitted answer is id-identical to the offline engine.
    """
    d = pts.shape[1]
    stream = _mix_stream("uniform", d, n_requests, seed)
    fe = Frontend(srv, queue_bound=queue_bound, batch_max=32,
                  batch_window_s=0.001).start()
    reqs = []
    for item in stream:
        if item[0] == "window":
            reqs.append(fe.submit_window(item[1], item[2]))
        else:
            reqs.append(fe.submit_knn(item[1], item[2]))
    fe.stop()

    errors = []
    if fe.stats.depth_peak > queue_bound:
        errors.append(
            f"queue depth {fe.stats.depth_peak} exceeded bound {queue_bound}"
        )
    dropped = [r for r in reqs if r.status != "ok"]
    if fe.stats.rejected == 0:
        errors.append("full-throttle burst produced zero rejections — "
                      "admission control never engaged")
    for r in dropped:
        if r.cert is None or r.cert.complete:
            errors.append(f"dropped request {r.seq} ({r.status}) lacks a "
                          "degraded certificate")
            break
    # admitted answers must match the same server queried offline
    served = [(r, it) for r, it in zip(reqs, stream) if r.status == "ok"]
    w = [(r, it) for r, it in served if it[0] == "window"][:32]
    if w:
        los = np.stack([it[1] for _, it in w])
        his = np.stack([it[2] for _, it in w])
        for (r, _), ref in zip(w, srv.window(los, his)):
            if not np.array_equal(np.sort(r.ids), np.sort(ref)):
                errors.append(f"window request {r.seq}: frontend ids "
                              "diverge from offline engine")
                break
    kq = [(r, it) for r, it in served if it[0] == "knn"][:32]
    if kq:
        qs = np.stack([it[1] for _, it in kq])
        for (r, _), ref in zip(kq, srv.knn(qs, K)):
            if not np.array_equal(r.ids, ref):
                errors.append(f"knn request {r.seq}: frontend ids diverge "
                              "from offline engine")
                break
    return {
        "burst_submitted": len(reqs),
        "burst_completed": fe.stats.completed,
        "burst_rejected": fe.stats.rejected,
        "burst_depth_peak": fe.stats.depth_peak,
        "burst_errors": errors,
    }


def run(n: int, *, duration_s: float, seed: int = 0) -> dict:
    pts, srv = _build_server(n, seed=seed)
    d = pts.shape[1]
    res: dict = {"n_points": n, "k": K}

    warm_server(srv, d)
    cap = measure_capacity(srv, d)
    res["capacity_qps"] = round(cap, 1)
    # a Python submit loop tops out well below true device capacity on
    # fast machines; cap the offered rate and record that we did, so the
    # "2x" label stays honest (the burst gate covers true saturation)
    max_offerable = 2000.0
    res["rate_capped"] = bool(2 * cap > max_offerable)

    for mix in ("hotspot", "uniform", "adversarial"):
        for label, mult in (("sub", 0.5), ("at", 1.0), ("2x", 2.0)):
            rate = min(cap * mult, max_offerable * (mult / 2.0))
            n_req = max(int(rate * duration_s), 32)
            stream = _mix_stream(
                mix, d, n_req,
                seed=zlib.crc32(f"{mix}/{label}".encode()) & 0xFFFF,
            )
            # over-capacity runs get a deadline + brownout so the queue
            # turns over instead of serializing the whole backlog at stop
            over = mult > 1.0
            stats, _reqs, _fe = run_open_loop(
                srv, stream, rate,
                queue_bound=256,
                # close batches once a full one could have arrived: a
                # window much shorter than the inter-arrival gap closes
                # 1-2 element batches that pad to pow2 and cost nearly a
                # full dispatch, collapsing effective capacity
                batch_window_s=min(64.0 / rate, 0.25),
                deadline_s=2.0 if over else None,
                brownout_high=192 if over else None,
            )
            for k, v in stats.items():
                res[f"{mix}_{label}_{k}"] = v
    burst = saturation_burst(srv, pts)
    res.update({k: v for k, v in burst.items() if k != "burst_errors"})
    res["burst_ok"] = not burst["burst_errors"]
    if burst["burst_errors"]:
        res["burst_error_detail"] = "; ".join(burst["burst_errors"])
    return res


def smoke_gate(res: dict, use_baselines: bool = True) -> list[str]:
    baselines = {}
    if use_baselines and BENCH_SERVE.exists():
        baselines = json.loads(BENCH_SERVE.read_text())
    failures = []
    if not res.get("burst_ok"):
        failures.append("saturation burst contract: "
                        + res.get("burst_error_detail", "?"))
    for key, floor_ms in SMOKE_GATED_LATENCY.items():
        got = res.get(key, -1.0)
        if got < 0:
            failures.append(f"{key}: missing/errored")
            continue
        base = baselines.get(f"smoke_{key}", -1.0)
        if base > 0:
            limit = max(base * (1 + SMOKE_REGRESSION_FRAC), base + floor_ms)
            if got > limit:
                failures.append(
                    f"{key}: {got:.1f}ms > {limit:.1f}ms "
                    f"(committed smoke baseline {base:.1f}ms +30%)"
                )
        else:
            ceiling = (SMOKE_CEILING_P99_MS if "p99" in key
                       else SMOKE_CEILING_P50_MS)
            if got > ceiling:
                failures.append(f"{key}: {got:.1f}ms > static ceiling "
                                f"{ceiling:.1f}ms (no committed baseline)")
    for key in SMOKE_GATED_THROUGHPUT:
        got = res.get(key, -1.0)
        base = baselines.get(f"smoke_{key}", -1.0)
        if base > 0 and got >= 0 and got < base * (1 - SMOKE_THROUGHPUT_FRAC):
            failures.append(
                f"{key}: {got:.1f} qps < {base * (1 - SMOKE_THROUGHPUT_FRAC):.1f} "
                f"(committed smoke baseline {base:.1f} qps -40%)"
            )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced scale, gate against baselines, no write")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds per (mix, rate) run")
    args = ap.parse_args(argv)
    compile_cache.enable()

    n = args.n or (SMOKE_N if args.smoke else FULL_N)
    duration = args.duration or (1.5 if args.smoke else 5.0)
    res = run(n, duration_s=duration)
    for k, v in sorted(res.items()):
        print(f"  {k:36s} {v}")

    if args.smoke:
        failures = smoke_gate(res, use_baselines=(n == SMOKE_N))
        if failures:
            print("SMOKE FAIL:\n  " + "\n  ".join(failures))
            return 1
        print("SMOKE OK")
        return 0

    if not res.get("burst_ok"):
        print("BURST GATE FAIL: " + res.get("burst_error_detail", "?"))
        return 1

    # record smoke-scale baselines for the CI gate next to the full numbers
    smoke_res = run(SMOKE_N, duration_s=1.5)
    for key in list(SMOKE_GATED_LATENCY) + sorted(SMOKE_GATED_THROUGHPUT):
        if key in smoke_res:
            res[f"smoke_{key}"] = smoke_res[key]

    atomic_write_json(BENCH_SERVE, res)
    print(f"wrote {BENCH_SERVE}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

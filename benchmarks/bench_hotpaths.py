"""Hot-path wall-clock benchmark: the scan engine's regression baseline.

Times the four paths the vectorized scan engine owns —

  * Step-2 routing + distribution (MST route, prefix-sum buffer replay,
    subspace gather),
  * Step-3 refinement (presorted minor-SplitTree recursion),
  * single + batched window queries (flat-table frontier traversal),
  * single + batched k-NN queries (vectorized leaf-table pruning),

plus the end-to-end ``bulk_load`` and the JAX candidate-leaf
``window_count``, and writes the numbers to ``BENCH_CORE.json`` at the repo
root.  Future perf PRs diff against that file.

It also times the compiled device query engine (``queries_jax``) on the
same workload, recording ``*_jax_s`` entries next to the CPU-engine
numbers, and the sharded device engine (``distributed_jax``, 4-way
partition behind the subspace-MBB router) as ``*_sharded_*`` entries.
Streaming ingest (PR-9) records sustained insert throughput through the
serving stack (``ingest_sustained_points_per_s`` — a rate, gated from
below) and the 64-window batch latency over the resulting multi-tier
state (``ingest_query_batch_64_s``).

  PYTHONPATH=src python -m benchmarks.bench_hotpaths            # full, writes BENCH_CORE.json
  PYTHONPATH=src python -m benchmarks.bench_hotpaths --smoke    # quick gate, no write

``--smoke`` runs a reduced dataset and fails (exit 1) when a named hot path
(bulk_load, window_batch, knn_batch) regresses more than 30% against the
smoke-scale baselines committed in BENCH_CORE.json (recorded by the full
run under ``smoke_*`` keys), with a small absolute floor so container
timing noise cannot trip the gate on its own.  Paths without a committed
baseline fall back to the static ceilings — a coarse tripwire for
interpreter-loop reintroductions, not a precision benchmark.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

from repro import compile_cache
from repro.core import (
    PageStore,
    bulk_load,
    knn_query,
    knn_query_batch,
    window_query,
    window_query_batch,
)
from repro.core.datasets import osm_like
from repro.core.ioutil import atomic_write_json
from repro.core.fmbi import _distribute_vectorized, refine_subspace
from repro.core.pagestore import branch_capacity, leaf_capacity
from repro.core.splittree import build_group_median_tree

from .common import buffer_pages

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH_CORE = ROOT / "BENCH_CORE.json"

# seed (pre-vectorization, commit b71a949) wall clock for bulk_load on the
# 600k OSM-like dataset on the reference container — the baseline the
# >= 5x acceptance criterion is measured against
SEED_BULK_LOAD_600K_S = 5.31

# --smoke ceilings (seconds): an order of magnitude above current numbers;
# only a reintroduced interpreter loop should trip these
SMOKE_CEILINGS_S = {
    "step2_route_distribute": 1.0,
    "refine": 1.5,
    "bulk_load": 4.0,
    "window_single": 2.0,
    "window_batch": 1.5,
    "knn_single": 2.0,
    "knn_batch": 1.5,
    "window_batch_fused": 1.5,
    "knn_batch_fused": 1.5,
    "window_batch_sharded": 2.0,
    "knn_batch_sharded": 2.0,
    "adaptive_serve_first": 8.0,
    "adaptive_serve_steady": 1.5,
    "adaptive_recovery": 8.0,
    "ingest_query": 2.0,
}

# hot paths gated against the committed smoke-scale baselines: >30%
# regression (plus an absolute noise floor) fails CI
SMOKE_GATED = {
    "bulk_load": "bulk_load_s",
    "window_batch": "window_batch_64_s",
    "knn_batch": "knn_batch_64_k16_s",
    "window_batch_fused": "window_batch_fused_64_s",
    "knn_batch_fused": "knn_batch_fused_64_k16_s",
    "window_batch_sharded": "window_batch_sharded_64_s",
    "knn_batch_sharded": "knn_batch_sharded_64_k16_s",
    "adaptive_serve_first": "adaptive_serve_first_result_s",
    "adaptive_serve_steady": "adaptive_serve_steady_batch_64_s",
    "adaptive_recovery": "adaptive_recovery_s",
    "ingest_sustained": "ingest_sustained_points_per_s",
    "ingest_query": "ingest_query_batch_64_s",
}
# gated entries that are rates (higher is better): the gate inverts — a
# fresh run fails when it lands >30% BELOW the committed baseline
SMOKE_RATE_GATED = {"ingest_sustained"}
# static floors for rate paths with no committed baseline (points/s)
SMOKE_RATE_FLOORS = {"ingest_sustained": 2_000.0}
SMOKE_REGRESSION_FRAC = 0.30
SMOKE_NOISE_FLOOR_S = 0.05
# one-shot cold-start paths carry jit-compile variance well above the
# default floor; a regression that matters there costs seconds, not 100ms
SMOKE_NOISE_FLOOR_OVERRIDES_S = {
    "adaptive_serve_first": 0.5,
    "adaptive_recovery": 0.5,
}
SMOKE_N = 120_000


def _timed(fn, repeats: int = 1) -> float:
    best = np.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(n: int = 600_000, seed: int = 0, repeats: int = 3) -> dict:
    pts = osm_like(n, seed=seed)
    d = pts.shape[1]
    c_l, c_b = leaf_capacity(d), branch_capacity(d)
    M = buffer_pages(pts)
    alpha = max(M // c_b, 1)
    if n <= c_b * alpha * c_l:
        raise SystemExit(
            f"n={n} is smaller than one Step-1 sample "
            f"({c_b * alpha * c_l} points); use a larger --n"
        )
    results: dict[str, float] = {}

    # ---- Step-2 routing + distribution (isolated) -----------------------
    sample = c_b * alpha * c_l
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(pts))
    samp_idx, rest_idx = np.sort(perm[:sample]), np.sort(perm[sample:])
    mst, _, samp_assign = build_group_median_tree(
        pts[samp_idx], n_groups=c_b, group_pages=alpha, page_points=c_l
    )

    def step2():
        assign = mst.route(pts[rest_idx])
        _distribute_vectorized(
            assign, rest_idx, samp_idx, samp_assign,
            c_b, c_l, M, alpha, PageStore(M),
        )

    results["step2_route_distribute_s"] = _timed(step2, repeats)

    # ---- Step-3 refine (isolated, one buffer-sized subspace per run) ----
    assign = mst.route(pts[rest_idx])
    sub_idx, *_ = _distribute_vectorized(
        assign, rest_idx, samp_idx, samp_assign,
        c_b, c_l, M, alpha, PageStore(M),
    )

    def refine():
        store = PageStore(M)
        for s in range(c_b):
            if len(sub_idx[s]):
                refine_subspace(pts, sub_idx[s], c_l, c_b, store)

    results["refine_s"] = _timed(refine, repeats)

    # ---- end-to-end bulk load -------------------------------------------
    results["bulk_load_s"] = _timed(lambda: bulk_load(pts, M, PageStore(M)),
                                    repeats)
    results["seed_bulk_load_600k_s"] = SEED_BULK_LOAD_600K_S
    if n == 600_000:
        results["bulk_load_speedup_vs_seed"] = round(
            SEED_BULK_LOAD_600K_S / results["bulk_load_s"], 2
        )

    # ---- query paths (single + batched) ---------------------------------
    idx = bulk_load(pts, M, PageStore(M))
    qrng = np.random.default_rng(1)
    centers = qrng.random((64, d)) * 0.9
    los, his = centers - 0.02, centers + 0.02
    results["window_single_64_s"] = _timed(
        lambda: [window_query(idx, los[i], his[i]) for i in range(64)],
        repeats,
    )
    results["window_batch_64_s"] = _timed(
        lambda: window_query_batch(idx, los, his), repeats
    )
    qs = qrng.random((64, d))
    results["knn_single_64_k16_s"] = _timed(
        lambda: [knn_query(idx, qs[i], 16) for i in range(64)], repeats
    )
    results["knn_batch_64_k16_s"] = _timed(
        lambda: knn_query_batch(idx, qs, 16), repeats
    )

    # ---- compiled device query engine (NodeTable -> DeviceTable) --------
    from repro.core.queries_jax import (
        DeviceTable,
        knn_query_batch_jax,
        window_query_batch_jax,
    )

    dev = DeviceTable.from_index(idx)
    window_query_batch_jax(dev, los, his)  # compile
    results["window_batch_64_jax_s"] = _timed(
        lambda: window_query_batch_jax(dev, los, his), repeats
    )
    knn_query_batch_jax(dev, qs, 16)  # compile
    results["knn_batch_64_k16_jax_s"] = _timed(
        lambda: knn_query_batch_jax(dev, qs, 16), repeats
    )

    # fused traversal+scan (PR-7 second-gen path) — explicit pin so the
    # gate survives a REPRO_FUSED default flip, plus the first-gen
    # baseline for the before/after diff
    results["window_batch_fused_64_s"] = _timed(
        lambda: window_query_batch_jax(dev, los, his, fused=True),
        repeats,
    )
    results["knn_batch_fused_64_k16_s"] = _timed(
        lambda: knn_query_batch_jax(dev, qs, 16, fused=True), repeats
    )
    window_query_batch_jax(dev, los, his, fused=False)  # compile
    results["window_batch_unfused_64_s"] = _timed(
        lambda: window_query_batch_jax(dev, los, his, fused=False),
        repeats,
    )
    knn_query_batch_jax(dev, qs, 16, fused=False)  # compile
    results["knn_batch_unfused_64_k16_s"] = _timed(
        lambda: knn_query_batch_jax(dev, qs, 16, fused=False), repeats
    )

    # bf16 compressed-MBB layout (half-width traversal bounds,
    # certified f32 re-check)
    dev_c = DeviceTable.from_index(idx, compressed=True)
    window_query_batch_jax(dev_c, los, his, fused=True)  # compile
    results["window_batch_fused_bf16_64_s"] = _timed(
        lambda: window_query_batch_jax(dev_c, los, his, fused=True),
        repeats,
    )
    knn_query_batch_jax(dev_c, qs, 16, fused=True)  # compile
    results["knn_batch_fused_bf16_64_k16_s"] = _timed(
        lambda: knn_query_batch_jax(dev_c, qs, 16, fused=True), repeats
    )

    # bytes the fused kernels move on this workload over the measured wall
    # clock: an achieved rate on whatever backend ran, not a roofline
    # share (a share needs a chip timing; see roofline.kernel_roofline)
    from repro import roofline as rf

    lo_np = np.asarray(dev.leaf_lo)
    hi_np = np.asarray(dev.leaf_hi)
    lf = los.astype(np.float32)
    hf = his.astype(np.float32)
    hit = np.all(
        (lo_np[None] <= hf[:, None]) & (hi_np[None] >= lf[:, None]),
        axis=2,
    )
    p0 = int(hit.sum())
    n_boxes = dev.n_leaves + sum(lv[0].shape[0] for lv in dev.levels)
    s = dev.leaf_size
    w_bytes = rf.bytes_box_hits_tiled(
        n_boxes, 64, d
    ) + rf.bytes_pair_window_ids(p0, s, d)
    results["window_fused_pairs"] = p0
    results["window_fused_bytes_moved"] = w_bytes
    results["window_fused_cpu_gbps"] = round(
        w_bytes / results["window_batch_fused_64_s"] / 1e9, 3
    )
    c0 = 8  # first-round candidate leaves per query (k=16, s>=32)
    k_bytes = rf.bytes_leaf_mindist_tiled(
        64, dev.n_leaves, d
    ) + rf.bytes_pair_dist2(64 * c0, s, d)
    results["knn_fused_bytes_moved"] = k_bytes
    results["knn_fused_cpu_gbps"] = round(
        k_bytes / results["knn_batch_fused_64_k16_s"] / 1e9, 3
    )

    # ---- sharded device engine (4-way partition + MBB router) ------------
    from repro.core.distributed_jax import (
        ShardedDeviceTable,
        knn_query_batch_sharded,
        window_query_batch_sharded,
    )

    sdev = ShardedDeviceTable.from_index(idx, 4)
    window_query_batch_sharded(sdev, los, his)  # compile
    results["window_batch_sharded_64_s"] = _timed(
        lambda: window_query_batch_sharded(sdev, los, his), repeats
    )
    knn_query_batch_sharded(sdev, qs, 16)  # compile
    results["knn_batch_sharded_64_k16_s"] = _timed(
        lambda: knn_query_batch_sharded(sdev, qs, 16), repeats
    )

    # ---- adaptive device serving (hotspot workload) ----------------------
    # time-to-first-result: boot DeviceQueryServer from the
    # single-unrefined-root AMBI state and answer the first hotspot batch
    # (host refinement + delta upload included); steady state: the same
    # hotspot batch once the hot set is resident (pure device dispatch)
    from repro.core import AMBI
    from repro.serve.engine import DeviceQueryServer

    hot_c = qrng.random((64, d)) * 0.08 + 0.45
    hot_c = hot_c.astype(np.float32).astype(np.float64)
    hot_lo, hot_hi = hot_c - 0.02, hot_c + 0.02

    def first_result():
        ambi = AMBI(pts, M)
        srv = DeviceQueryServer.from_ambi(ambi, microbatch=64)
        srv.window(hot_lo, hot_hi)
        return srv

    t0 = time.perf_counter()
    adaptive_srv = first_result()
    results["adaptive_serve_first_result_s"] = time.perf_counter() - t0
    adaptive_srv.window(hot_lo, hot_hi)  # compile/warm the hot path
    results["adaptive_serve_steady_batch_64_s"] = _timed(
        lambda: adaptive_srv.window(hot_lo, hot_hi), repeats
    )
    results["adaptive_serve_cold_queries"] = (
        adaptive_srv.stats.cold_queries
    )
    results["adaptive_serve_grafts"] = adaptive_srv.stats.grafts

    # ---- adaptive crash recovery (snapshot + journal replay reboot) ------
    # a durable adaptive server journals the hotspot batch's cold ops;
    # `recover` then reboots it — snapshot load, journal replay against
    # the restored rng/page-store state, and the device re-export — and
    # must land on the bit-identical table (asserted, not just timed)
    import shutil
    import tempfile

    from repro.core import AMBI
    from repro.serve.engine import DeviceQueryServer

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="bench_recovery_"))
    try:
        srv = DeviceQueryServer.from_ambi(
            AMBI(pts, M), microbatch=64,
            journal_path=tmp / "ops.journal",
            snapshot_path=tmp / "snap.npz",
        )
        srv.window(hot_lo, hot_hi)
        results["adaptive_recovery_journal_records"] = (
            srv.stats.journal_records
        )
        t0 = time.perf_counter()
        recovered = DeviceQueryServer.recover(
            tmp / "snap.npz", tmp / "ops.journal", microbatch=64
        )
        results["adaptive_recovery_s"] = time.perf_counter() - t0
        if not recovered.ambi.table.equals(srv.ambi.table):
            raise RuntimeError(
                "recovered table diverged from the live server"
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # ---- streaming ingest (LSM tiers, delta-only device refresh) ---------
    # sustained throughput: batched inserts through the serving stack —
    # memtable appends, flushes, tier merges AND the incremental device
    # refresh after each mutation; then the 64-window batch latency on the
    # resulting multi-tier state (what a reader pays mid-ingest)
    from repro.core import StreamingIndex
    from repro.serve.engine import DeviceQueryServer

    stream = StreamingIndex(pts, buffer_pages=M)
    ingest_srv = DeviceQueryServer.from_streaming(stream, microbatch=64)
    ingest_n = min(32_768, max(4_096, n // 16))
    irng = np.random.default_rng(5)
    feed = irng.random((ingest_n, d))
    t0 = time.perf_counter()
    for off in range(0, ingest_n, 1024):
        ingest_srv.insert(feed[off:off + 1024])
    dt = time.perf_counter() - t0
    results["ingest_sustained_points_per_s"] = round(ingest_n / dt, 1)
    results["ingest_flushes"] = stream.flushes
    results["ingest_tier_merges"] = stream.merges + stream.fusions
    ingest_srv.window(los, his)  # compile/warm on the final tier shapes
    results["ingest_query_batch_64_s"] = _timed(
        lambda: ingest_srv.window(los, his), repeats
    )

    # ---- JAX candidate-leaf window_count --------------------------------
    import jax.numpy as jnp

    from repro.core import jax_index

    levels = 10
    padded, ids = jax_index.pad_points(pts.astype(np.float32), levels)
    jidx = jax_index.build(jnp.asarray(padded), levels,
                           jnp.asarray(ids, np.int32))
    jl = jnp.asarray(los.astype(np.float32))
    jh = jnp.asarray(his.astype(np.float32))
    jax_index.window_count(jidx, jl, jh)  # compile
    results["jax_window_count_64_s"] = _timed(
        lambda: jax_index.window_count(jidx, jl, jh).block_until_ready(),
        repeats,
    )

    return results


def run_scale(n: int = 10_000_000, seed: int = 7) -> dict:
    """10M-point scaling gate: end-to-end bulk load, fused device queries,
    and sampled parity against the NumPy engine.

    Recorded under ``*_10m_s`` keys in BENCH_CORE.json.  Parity is asserted,
    not just timed: a divergence raises and the run exits non-zero.
    """
    results: dict[str, float] = {}
    pts = osm_like(n, seed=seed)
    d = pts.shape[1]
    M = buffer_pages(pts)
    t0 = time.perf_counter()
    idx = bulk_load(pts, M, PageStore(M))
    results["bulk_load_10m_s"] = time.perf_counter() - t0

    from repro.core.queries_jax import (
        DeviceTable,
        knn_query_batch_jax,
        window_query_batch_jax,
    )

    dev = DeviceTable.from_index(idx, compressed=True)
    qrng = np.random.default_rng(11)
    centers = qrng.random((64, d)) * 0.9
    los, his = centers - 0.01, centers + 0.01
    qs = qrng.random((64, d))
    window_query_batch_jax(dev, los, his, fused=True)  # compile
    results["window_batch_64_jax_10m_s"] = _timed(
        lambda: window_query_batch_jax(dev, los, his, fused=True), 2
    )
    knn_query_batch_jax(dev, qs, 16, fused=True)  # compile
    results["knn_batch_64_k16_jax_10m_s"] = _timed(
        lambda: knn_query_batch_jax(dev, qs, 16, fused=True), 2
    )

    # sampled parity vs the NumPy engine (8 windows + 8 knn queries)
    got_w = window_query_batch_jax(dev, los[:8], his[:8], fused=True)
    ref_w, _ = window_query_batch(idx, los[:8], his[:8])
    for a, b in zip(ref_w, got_w):
        if set(np.asarray(a).tolist()) != set(np.asarray(b).tolist()):
            raise RuntimeError("10M window parity diverged")
    got_k = knn_query_batch_jax(dev, qs[:8], 16, fused=True)
    ref_k, _ = knn_query_batch(idx, qs[:8], 16)
    for a, b in zip(ref_k, got_k):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise RuntimeError("10M knn parity diverged")
    results["scale_10m_parity"] = 1.0
    results["scale_10m_n_leaves"] = dev.n_leaves
    return results


def smoke_gate(res: dict, use_baselines: bool = True) -> list[str]:
    """Diff fresh smoke timings against the committed baselines.

    A named hot path fails when it exceeds the committed ``smoke_<key>``
    value by more than ``SMOKE_REGRESSION_FRAC`` *and* by more than the
    absolute noise floor.  Paths without a committed baseline (older
    BENCH_CORE.json, a missing file, or a ``--n`` override that makes the
    workload incomparable to the SMOKE_N baselines) fall back to the
    static ceilings.
    """
    baselines = {}
    if use_baselines and BENCH_CORE.exists():
        baselines = json.loads(BENCH_CORE.read_text())
    failures = []
    for name, key in SMOKE_GATED.items():
        got = res[key]
        base = baselines.get(f"smoke_{key}", -1.0)
        if name in SMOKE_RATE_GATED:  # higher is better: gate the floor
            if base > 0:
                limit = base * (1 - SMOKE_REGRESSION_FRAC)
                if got < limit:
                    failures.append(
                        f"{name}: {got:.1f}/s < {limit:.1f}/s "
                        f"(committed smoke baseline {base:.1f}/s -30%)"
                    )
            elif got < SMOKE_RATE_FLOORS[name]:
                failures.append(
                    f"{name}: {got:.1f}/s < static floor "
                    f"{SMOKE_RATE_FLOORS[name]:.1f}/s (no committed baseline)"
                )
            continue
        if base > 0:
            floor = SMOKE_NOISE_FLOOR_OVERRIDES_S.get(
                name, SMOKE_NOISE_FLOOR_S
            )
            limit = max(base * (1 + SMOKE_REGRESSION_FRAC), base + floor)
            if got > limit:
                failures.append(
                    f"{name}: {got:.3f}s > {limit:.3f}s "
                    f"(committed smoke baseline {base:.3f}s +30%)"
                )
        elif got > SMOKE_CEILINGS_S[name]:
            failures.append(
                f"{name}: {got:.3f}s > static ceiling "
                f"{SMOKE_CEILINGS_S[name]:.3f}s (no committed baseline)"
            )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced size, gate against ceilings, no JSON write")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--scale-n", type=int, default=10_000_000,
                    help="10M scaling-gate size for the full run")
    ap.add_argument("--no-scale", action="store_true",
                    help="skip the 10M scaling gate in the full run")
    args = ap.parse_args(argv)
    compile_cache.enable()

    n = args.n or (SMOKE_N if args.smoke else 600_000)
    # smoke takes best-of-2 so one scheduler hiccup cannot trip the
    # 30%-regression gate against the best-of-3 committed baselines
    res = run(n=n, repeats=2 if args.smoke else 3)
    res["n_points"] = n
    for k, v in sorted(res.items()):
        print(f"  {k:32s} {v}")

    if args.smoke:
        failures = smoke_gate(res, use_baselines=(n == SMOKE_N))
        checks = {
            "step2_route_distribute": res["step2_route_distribute_s"],
            "refine": res["refine_s"],
            "window_single": res["window_single_64_s"],
            "knn_single": res["knn_single_64_k16_s"],
        }
        for name, got in checks.items():
            if got > SMOKE_CEILINGS_S[name]:
                failures.append(
                    f"{name}: {got:.3f}s > ceiling "
                    f"{SMOKE_CEILINGS_S[name]:.3f}s"
                )
        if failures:
            print("SMOKE FAIL:\n  " + "\n  ".join(failures))
            return 1
        print("SMOKE OK")
        return 0

    # 10M scaling gate: bulk load + fused device queries + sampled parity
    if not args.no_scale:
        scale = run_scale(n=args.scale_n)
        res.update(scale)
        for k, v in sorted(scale.items()):
            print(f"  {k:32s} {v}")

    # record smoke-scale baselines for the CI regression gate alongside the
    # full-scale numbers (same container, best-of-repeats)
    smoke_res = run(n=SMOKE_N, repeats=3)
    for key in SMOKE_GATED.values():
        res[f"smoke_{key}"] = smoke_res[key]

    # merge over the committed file: keys this run skipped (e.g. the 10M
    # scaling numbers under --no-scale) must survive the rewrite
    out = {}
    if BENCH_CORE.exists():
        out = json.loads(BENCH_CORE.read_text())
    out.update(res)
    atomic_write_json(BENCH_CORE, out)
    print(f"wrote {BENCH_CORE}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

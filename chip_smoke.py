#!/usr/bin/env python3
"""Smoke run of the served index path on a TPU, through its public entry points.

    python chip_smoke.py                  # one chip (the default)
    python chip_smoke.py --four-chips     # the Section-5 collective rounds

One chip: an ``osm_like`` 10M-point index is bulk loaded on the host
(FMBI), booted with ``DeviceQueryServer.from_index`` and served through a
``Frontend``: a few hundred hot-spot windows plus k-NN requests (k=16).
Every request must end ``ok``, and a sample of the answers must match the
NumPy engine (windows as id sets; k-NN ids under the engines' tie rule).
A short adaptive phase then boots ``DeviceQueryServer.from_ambi`` over the
same points and serves one hot-spot batch twice: the second pass must be
device-only and match the NumPy engine too.

Four chips (``--four-chips``, and nothing else): a 4-shard
``ShardedDeviceTable`` is stacked and placed one shard per chip, and the
``shard_map`` k-NN and window-count rounds are compared with the one-chip
``DeviceTable`` engine.

The script runs in one process and starts none: a process that has
touched JAX holds the chip.  Without a TPU, or with
``REPRO_PALLAS_INTERPRET`` set, it exits non-zero before any phase.  The
times it prints are smoke timings, not benchmark numbers.  The last line
of standard output is one JSON object, ``{"ok": true, "device": ...}``;
any failed check exits non-zero before it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import buffer_pages  # noqa: E402
from repro import compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    AMBI,
    PageStore,
    bulk_load,
    knn_query_batch,
    window_query_batch,
)
from repro.core.datasets import osm_like  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve.engine import DeviceQueryServer  # noqa: E402
from repro.serve.frontend import Frontend  # noqa: E402

K = 16
MICROBATCH = 64
N_POINTS = 10_000_000
N_WINDOWS = 256
N_KNN = 64
PARITY_SAMPLE = 64  # windows and k-NN answers checked against NumPy
REQUEST_TIMEOUT_S = 600.0


class SmokeFailure(Exception):
    """A check of the smoke run failed; the message says which."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def f32_exact(a: np.ndarray) -> np.ndarray:
    """Round to float32, the precision the device table stores, and keep
    float64: the NumPy engine and the device then compare equal values."""
    return np.asarray(a, dtype=np.float32).astype(np.float64)


# -- (a) the device gate ---------------------------------------------------
def device_gate():
    """The first device, if it is a TPU that runs the kernels compiled."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    check(env in ("", "0", "false", "False"),
          f"REPRO_PALLAS_INTERPRET={env!r} asks for interpret-mode "
          "kernels; a chip run compiles them")
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"needs a TPU, but JAX found platform {dev.platform!r} "
          f"({dev.device_kind})")
    check(not ops.interpret_default(),
          "the kernels would run in interpret mode on this TPU")
    return dev


class CompileClock:
    """Seconds spent in XLA backend compiles while it is installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


# -- workload --------------------------------------------------------------
def hotspot_centre(pts: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """The middle of the hot spot: a point of the dataset, so that the hot
    spot follows the data.  (``benchmarks.bench_serving`` fixes its hot
    square at [0.45, 0.53]^2, which lies in ``osm_like``'s empty ocean
    band: its windows return nothing.)"""
    return pts[rng.integers(len(pts))]


def hotspot_points(n: int, centre: np.ndarray,
                   rng: np.random.Generator) -> np.ndarray:
    """Points drawn uniformly from the 0.08-wide square around ``centre``,
    the hot-spot geometry of ``benchmarks.bench_serving``."""
    c = centre - 0.04 + rng.random((n, len(centre))) * 0.08
    return f32_exact(np.clip(c, 0, 1))


def hotspot_windows(n: int, centre: np.ndarray, rng: np.random.Generator):
    """Windows of half-width 0.02 around hot-spot points."""
    c = hotspot_points(n, centre, rng)
    return f32_exact(np.clip(c - 0.02, 0, 1)), f32_exact(np.clip(c + 0.02, 0, 1))


# -- (b) build and boot ----------------------------------------------------
def build_index(n: int, seed: int):
    """Host FMBI bulk load of ``osm_like(n, seed)``; returns the index and
    its build seconds."""
    pts = f32_exact(osm_like(n, seed=seed))
    m = buffer_pages(pts)
    t0 = time.perf_counter()
    idx = bulk_load(pts, m, PageStore(m))
    return idx, time.perf_counter() - t0


def boot_server(idx, use_kernel=None):
    t0 = time.perf_counter()
    srv = DeviceQueryServer.from_index(idx, microbatch=MICROBATCH,
                                       use_kernel=use_kernel)
    jax.block_until_ready(srv.dev.leaf_pts)
    return srv, time.perf_counter() - t0


def leaf_table_bytes(dev) -> dict:
    """Bytes of the ``(d, L, S)`` leaf table: as the array's shape needs
    them, and as the device stores them (a narrow minor axis may be
    padded out to full lanes)."""
    lp = dev.leaf_pts
    logical = int(np.prod(lp.shape)) * lp.dtype.itemsize
    stored = int(lp.on_device_size_in_bytes())
    return {"shape": tuple(lp.shape), "logical_bytes": logical,
            "device_bytes": stored, "ratio": stored / logical}


def compiled_stages(srv) -> dict:
    """Compile the served path's jitted stages for this backend at a full
    microbatch and the table's shapes.  Per stage: whether it holds a
    Mosaic kernel (``tpu_custom_call``) rather than interpreted code, and
    its temporary device bytes (where the kernels need the leaf table in
    another layout than the one it is stored in, the copy lands here)."""
    from repro.core import queries_jax as qj

    dev, use_kernel = srv.dev, srv.use_kernel
    q = jax.ShapeDtypeStruct((MICROBATCH, srv.dim), np.float32)
    scalar = jax.ShapeDtypeStruct((), np.int32)
    hits = jax.ShapeDtypeStruct((MICROBATCH, dev.n_leaves + dev.n_cold),
                                np.bool_)
    lowered = {
        "frontier": qj._frontier_count.lower(dev, q, q, use_kernel),
        "window_scan": qj._fused_pack_scan.lower(
            dev, q, q, hits, scalar, qj.PAIR_CHUNK, use_kernel),
        # the first k-NN round scans 8 candidate leaves per query at k=16
        "knn_round": qj._knn_core_fused.lower(dev, q, scalar, K, 8,
                                              use_kernel),
    }
    out = {}
    for name, low in lowered.items():
        exe = low.compile()
        out[name] = {"kernel": "tpu_custom_call" in exe.as_text(),
                     "temp_bytes": int(exe.memory_analysis().temp_size_in_bytes)}
    return out


def first_batches(srv, rng: np.random.Generator) -> float:
    """One full microbatch of each kind on a cold process: the seconds it
    takes, compiles included."""
    centre = hotspot_centre(srv.points, rng)
    los, his = hotspot_windows(MICROBATCH, centre, rng)
    qs = hotspot_points(MICROBATCH, centre, rng)
    t0 = time.perf_counter()
    srv.window(los, his)
    srv.knn(qs, K)
    return time.perf_counter() - t0


# -- (c) serve through the frontend ----------------------------------------
def serve_requests(srv, n_windows: int, n_knn: int, seed: int):
    """Submit hot-spot windows and k-NN requests (three windows to one
    k-NN, interleaved) to a started ``Frontend`` and wait for all of them.
    Every request must end ``ok``.  Returns the workload and the requests."""
    rng = np.random.default_rng(seed)
    centre = hotspot_centre(srv.points, rng)
    los, his = hotspot_windows(n_windows, centre, rng)
    qs = hotspot_points(n_knn, centre, rng)
    fe = Frontend(srv, queue_bound=n_windows + n_knn + 1,
                  batch_max=MICROBATCH).start()
    wreqs, kreqs = [], []
    try:
        wi = ki = 0
        while wi < n_windows or ki < n_knn:
            if ki < n_knn and (wi >= n_windows or wi >= 3 * (ki + 1)):
                kreqs.append(fe.submit_knn(qs[ki], K))
                ki += 1
            else:
                wreqs.append(fe.submit_window(los[wi], his[wi]))
                wi += 1
        for r in wreqs + kreqs:
            r.wait(REQUEST_TIMEOUT_S)
    finally:
        fe.stop()
    bad = [r for r in wreqs + kreqs if r.status != "ok"]
    check(not bad,
          f"{len(bad)} of {len(wreqs) + len(kreqs)} requests did not end ok; "
          + "; ".join(f"{r.kind} #{r.seq}: {r.status} ({r.reason})"
                      for r in bad[:5]))
    return (los, his, [r.ids for r in wreqs]), (qs, [r.ids for r in kreqs]), fe.stats


# -- (d) parity with the NumPy engine --------------------------------------
def window_parity(idx, los, his, got) -> int:
    """Every sampled window returns the NumPy engine's id set."""
    ref, _ = window_query_batch(idx, los, his)
    check(len(ref) == len(got), "window result count differs")
    for i, (a, b) in enumerate(zip(got, ref)):
        check(np.array_equal(np.sort(np.asarray(a)), np.sort(np.asarray(b))),
              f"window {i}: {len(a)} ids on the device, {len(b)} in the "
              "NumPy engine, or different ids")
    return sum(len(b) for b in ref)


def knn_matches(pts, q, got, want) -> bool:
    """k-NN answers agree when they hold the same sorted squared distances
    and the same ids below the k-th distance: exact ties are unspecified
    in both engines, so any point tied at the k-th distance may fill the
    last places."""
    got, want = np.asarray(got), np.asarray(want)
    if np.array_equal(got, want):
        return True
    if got.shape != want.shape or not len(got):
        return False
    dg = np.sum((pts[got] - q) ** 2, axis=1)
    dw = np.sum((pts[want] - q) ** 2, axis=1)
    if not np.array_equal(np.sort(dg), np.sort(dw)):
        return False
    kth = dw.max()
    return np.array_equal(np.sort(got[dg < kth]), np.sort(want[dw < kth]))


def knn_parity(idx, qs, got, k: int = K) -> int:
    """Every sampled k-NN answer matches the NumPy engine; returns how many
    were id-identical in order (the rest differ only among equal
    distances)."""
    ref, _ = knn_query_batch(idx, qs, k)
    check(len(ref) == len(got), "k-NN result count differs")
    same = 0
    for i, (a, b) in enumerate(zip(got, ref)):
        check(knn_matches(idx.points, qs[i], a, b),
              f"k-NN query {i}: device ids {np.asarray(a)[:k].tolist()} "
              f"vs NumPy ids {np.asarray(b)[:k].tolist()}")
        same += int(np.array_equal(a, b))
    return same


# -- (e) adaptive serving --------------------------------------------------
def adaptive_phase(pts, ref_idx, seed: int, use_kernel=None) -> dict:
    """Boot an unrefined AMBI server over ``pts`` and serve one hot-spot
    batch twice: the first pass refines on the host, the second must be
    answered by the device alone.  Both must match ``ref_idx``, an FMBI
    index over the same points."""
    m = buffer_pages(pts)
    t0 = time.perf_counter()
    srv = DeviceQueryServer.from_ambi(AMBI(pts, m), microbatch=MICROBATCH,
                                      use_kernel=use_kernel)
    boot_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed + 1)
    los, his = hotspot_windows(MICROBATCH, hotspot_centre(pts, rng), rng)
    t0 = time.perf_counter()
    first = srv.window(los, his)
    first_s = time.perf_counter() - t0
    cold = srv.stats.cold_queries
    check(cold > 0, "the first hot-spot batch reached no unrefined space")
    second = srv.window(los, his)
    check(srv.stats.cold_queries == cold,
          f"the second pass was not device-only: cold_queries went from "
          f"{cold} to {srv.stats.cold_queries}")
    n_ids = window_parity(ref_idx, los, his, first)
    window_parity(ref_idx, los, his, second)
    return {"n_points": len(pts), "boot_s": boot_s, "first_pass_s": first_s,
            "cold_queries": cold, "grafts": srv.stats.grafts,
            "windows": len(los), "ids_checked": n_ids,
            "use_kernel": srv.use_kernel}


# -- four chips: the shard_map collective rounds ----------------------------
def four_chip_phase(idx, seed: int, n_queries: int = 32) -> dict:
    """Stack a 4-shard table one shard per device, run the collective k-NN
    and window-count rounds, and compare them with the one-device engine:
    the same k-NN ids, and window counts equal to the lengths of its
    window id lists."""
    from repro.core.distributed_jax import (
        STACKED_KEYS,
        ShardedDeviceTable,
        knn_batch_shard_map,
        place_stacked,
        window_count_batch_shard_map,
    )
    from repro.core.queries_jax import (
        DeviceTable,
        knn_query_batch_jax,
        window_query_batch_jax,
    )

    devices = jax.devices()[:4]
    check(len(devices) == 4, f"needs 4 devices, JAX found {len(devices)}")
    mesh = jax.make_mesh((4,), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    stacked = ShardedDeviceTable.from_index(idx, 4).stacked()
    placed = place_stacked(stacked, mesh)
    device_sets = {}
    for key in STACKED_KEYS:
        arr = placed[key]
        owners = sorted(s.device.id for s in arr.addressable_shards)
        check(len(set(owners)) == 4 and arr.sharding.device_set == set(devices),
              f"{key} is not split one shard per device: {owners}")
        device_sets[key] = sorted(d.id for d in arr.sharding.device_set)
        report("four-chip", array=key, shape=arr.shape,
               device_set=device_sets[key],
               shard_shape=arr.addressable_shards[0].data.shape)

    rng = np.random.default_rng(seed + 2)
    centre = hotspot_centre(idx.points, rng)
    qs = hotspot_points(n_queries, centre, rng)
    los, his = hotspot_windows(n_queries, centre, rng)
    _, ids = knn_batch_shard_map(placed, qs, K, mesh)
    counts = window_count_batch_shard_map(placed, los, his, mesh)

    one = DeviceTable.from_index(idx)
    want_k = knn_query_batch_jax(one, qs, K)
    want_w = window_query_batch_jax(one, los, his)
    same = 0
    for i, (a, b) in enumerate(zip(ids, want_k)):
        check(knn_matches(idx.points, qs[i], a, b),
              f"k-NN query {i}: shard_map ids {a.tolist()} vs one-device "
              f"ids {b.tolist()}")
        same += int(np.array_equal(a, b))
    want_counts = np.array([len(w) for w in want_w])
    check(np.array_equal(counts, want_counts),
          f"window counts {counts.tolist()} vs one-device "
          f"{want_counts.tolist()}")
    return {"knn_queries": n_queries, "knn_id_identical": same,
            "windows": n_queries, "window_points": int(want_counts.sum()),
            "device_sets": device_sets}


# -- main --------------------------------------------------------------------
def run_one_chip(n: int, seed: int, device) -> None:
    with CompileClock() as clock:
        idx, build_s = build_index(n, seed)
        report("build", points=n, seed=seed,
               smoke_build_s=round(build_s, 3))
        srv, boot_s = boot_server(idx)
        check(srv.use_kernel, "the served path did not resolve use_kernel=True")
        stages = compiled_stages(srv)
        for name, st in stages.items():
            report("stage", name=name, mosaic_kernel=st["kernel"],
                   temp_bytes=st["temp_bytes"])
            check(st["kernel"], f"the compiled {name} stage holds no "
                  "tpu_custom_call")
        lt = leaf_table_bytes(srv.dev)
        report("boot", smoke_boot_s=round(boot_s, 3), use_kernel=srv.use_kernel,
               leaf_table=lt["shape"], leaf_table_logical_bytes=lt["logical_bytes"],
               leaf_table_device_bytes=lt["device_bytes"],
               leaf_table_padding=round(lt["ratio"], 3))
        first_s = first_batches(srv, np.random.default_rng(seed))
        report("first-batch", smoke_first_batch_s=round(first_s, 3),
               smoke_compile_s=round(clock.seconds, 3), compiles=clock.count,
               note="smoke timings, not benchmark numbers")

        (los, his, wres), (qs, kres), stats = serve_requests(
            srv, N_WINDOWS, N_KNN, seed)
        report("serve", windows=len(wres), knn=len(kres), k=K,
               completed=stats.completed, batches=stats.batches,
               dropped=stats.dropped)

        s = PARITY_SAMPLE
        n_ids = window_parity(idx, los[:s], his[:s], wres[:s])
        same = knn_parity(idx, qs[:s], kres[:s])
        report("parity", windows=min(s, len(wres)), window_ids=n_ids,
               knn=min(s, len(kres)), knn_id_identical=same)

        out = adaptive_phase(idx.points, idx, seed)
        report("adaptive", **{k: (round(v, 3) if isinstance(v, float) else v)
                              for k, v in out.items()})
        report("compile", smoke_compile_s=round(clock.seconds, 3),
               compiles=clock.count)

    mem = device.memory_stats() or {}
    report("memory", device_kind=device.device_kind,
           devices=len(jax.devices()),
           peak_bytes_in_use=mem.get("peak_bytes_in_use", "not reported"))


def run_four_chips(n: int, seed: int) -> None:
    idx, build_s = build_index(n, seed)
    report("build", points=n, seed=seed, smoke_build_s=round(build_s, 3))
    out = four_chip_phase(idx, seed)
    report("four-chip", knn_queries=out["knn_queries"],
           knn_id_identical=out["knn_id_identical"],
           windows=out["windows"], window_points=out["window_points"])
    for i, dev in enumerate(jax.devices()[:4]):
        mem = dev.memory_stats() or {}
        report("memory", device=i, device_kind=dev.device_kind,
               peak_bytes_in_use=mem.get("peak_bytes_in_use", "not reported"))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the dataset and the requests")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map rounds across four chips")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        device = device_gate()
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    compile_cache.enable()
    report("device", platform=device.platform, device_kind=device.device_kind,
           devices=len(jax.devices()))
    try:
        if args.four_chips:
            run_four_chips(N_POINTS, args.seed)
        else:
            run_one_chip(N_POINTS, args.seed, device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

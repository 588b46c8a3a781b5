"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the numbers.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration (``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<traffic>.json``, read by :mod:`bench.generator`) and its
per-layer metrics (``bench/metrics/<metric>.py``, each a ``read(ctx)``);
the configuration's ``boot`` (``"fmbi"`` where it names none) names how
its index is booted (``bench/boot/<boot>.py``, a ``boot(config, points,
batch_max)`` that returns the server and its build times).  Adding a
configuration, a boot, a mix or a metric adds files and entries; no code
here changes.

Set-up, which ``setup_s`` measures from the process's start: the
configuration's fixed data, the boot, and a warm-up (on a seed stream
apart from the window's): batches of the cell's own requests planned by
the engine's buckets (:mod:`bench.warmup`), then passes of its traffic
until one builds no new program.  A boot whose module sets
``CHANGES_AS_IT_SERVES`` (an adaptive index refines as it serves) is
warmed up on a second server booted alike, with the passes alone, so the
window starts from the state the boot made.  The window then drives a
started ``Frontend`` over the booted server for ``seconds``; open-loop
requests are timed from when each was due, closed-loop ones from when
their client sent them.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import heapq
import importlib.util
import json
import os
import pathlib
import time

import numpy as np

from bench import generator as gen

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
BATCH_MAX = 64          # the server's microbatch and the Frontend's batch_max
GRACE_S = 60.0          # how long after the window an answer may still come
STATUS_OK = "ok"


class SetupError(Exception):
    """The run cannot start: no chip, or a cell that does not resolve."""


# -- the manifest and the files it names -----------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list


def load_manifest(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.exists():
        raise SetupError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _for_cell(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def boot_name(config: dict) -> str:
    return config.get("boot", "fmbi")


def resolve_cell(manifest: dict, name: str,
                 root: pathlib.Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    boot = boot_name(config)
    if not (BENCH / "boot" / f"{boot}.py").is_file():
        raise SetupError(f"{name}: no boot {boot!r} "
                         f"(bench/boot/{boot}.py)")
    traffic = gen.load_traffic(w["traffic"], root / "bench" / "traffic")
    from bench import reference

    for m in traffic["mix"]:
        number = reference.NUMBER_OF_KIND[m["kind"]]
        if number not in reference.LIMITS:
            raise SetupError(f"{name}: {m['kind']} answers are compared by "
                             f"{number}, which has no limit measured yet")
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, _for_cell(manifest["end_to_end"], name),
                _for_cell(manifest["per_layer"], name))


def _load(path: pathlib.Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, metrics_dir: pathlib.Path = BENCH / "metrics"):
    """The ``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    return _load(metrics_dir / f"{metric}.py", "bench_metric_").read


def load_boot(name: str, boot_dir: pathlib.Path = BENCH / "boot"):
    """The module ``bench/boot/<name>.py``: its ``boot`` and its
    ``CHANGES_AS_IT_SERVES``."""
    return _load(boot_dir / f"{name}.py", "bench_boot_")


# -- the chip and the compile cache ------------------------------------------
def device_gate(chips: int):
    """The devices of a TPU that runs the kernels compiled, or SetupError."""
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"needs a TPU, but JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if ops.interpret_default():
        raise SetupError("the kernels would run in interpret mode")
    return devs


def enable_compile_cache(root: pathlib.Path = ROOT) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    it is set, else ``<checkout>/.jax_cache``; every program is cached,
    however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileClock:
    """Programs built (compiled, or loaded from the persistent cache) and
    the cache's hits and misses, while installed."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.builds, self.build_s, self.hits, self.misses = 0, 0.0, 0, 0
        self.by_function = collections.defaultdict(lambda: [0, 0.0])

    def _duration(self, event: str, duration: float, fun_name: str = "?",
                  **_kw) -> None:
        if event == self.BUILD:
            self.builds += 1
            self.build_s += duration
            self.by_function[fun_name][0] += 1
            self.by_function[fun_name][1] += duration

    def _event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.MISS:
            self.misses += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        return {"builds": self.builds, "build_s": self.build_s,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def by_function_text(self, since: dict | None = None) -> str:
        """``name:builds:seconds`` for each jitted function, most first;
        with ``since`` (an earlier ``by_function_copy``) only what came
        after it."""
        since = since or {}
        rows = []
        for k, (n, t) in self.by_function.items():
            n0, t0 = since.get(k, (0, 0.0))
            if n > n0:
                rows.append((k, n - n0, t - t0))
        rows.sort(key=lambda r: -r[2])
        return " ".join(f"{k}:{n}:{t:.3f}" for k, n, t in rows)

    def by_function_copy(self) -> dict:
        return {k: tuple(v) for k, v in self.by_function.items()}


# -- the served path ---------------------------------------------------------
def buffer_pages(config: dict, points: np.ndarray) -> int:
    """FMBI's buffer: ``buffer_fraction`` of the data's pages, at least a
    branch page's fanout plus one."""
    from repro.core.pagestore import branch_capacity, leaf_capacity

    n, d = points.shape
    pages = -(-n // leaf_capacity(d))
    return max(int(pages * float(config["buffer_fraction"])),
               branch_capacity(d) + 1)


def build_and_boot(config: dict, points: np.ndarray):
    """The configuration's boot, until its table is on the device.
    Returns the server and the build times (``bulk_load_s``,
    ``upload_s``)."""
    return load_boot(boot_name(config)).boot(config, points, BATCH_MAX)


def server_stats(server) -> dict:
    """The server's counters (``DeviceQueryStats``) as they stand."""
    return dataclasses.asdict(server.stats)


class TimedServer:
    """What the ``Frontend`` is handed: the server, with each ``window`` and
    ``knn`` call wrapped in a trace span and timed on the host clock."""

    def __init__(self, server):
        self._server = server
        self.calls: list = []  # (kind, queries, start, end)

    def __getattr__(self, name):
        return getattr(self._server, name)

    def _timed(self, kind, fn, n, *args, **kw):
        from jax.profiler import TraceAnnotation

        t0 = time.perf_counter()
        with TraceAnnotation(f"engine.{kind}"):
            out = fn(*args, **kw)
        self.calls.append((kind, n, t0, time.perf_counter()))
        return out

    def window(self, los, his, **kw):
        return self._timed("window", self._server.window, len(los), los, his,
                           **kw)

    def knn(self, qs, k, **kw):
        return self._timed("knn", self._server.knn, len(qs), qs, k, **kw)


# -- driving the window -------------------------------------------------------
class Sample:
    """The answers compared with the reference: of the requests answered
    ``ok``, the ``size`` with the least draws, one uniform draw per request
    index from the seed.  A uniform sample of the window's answers however
    many there are, and the same requests on a rerun of one seed."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng = int(size), rng
        self.u = np.zeros(0)
        self.heap: list = []  # (-draw, index) of the kept answers

    def draw(self, i: int) -> float:
        while i >= len(self.u):
            self.u = np.concatenate([self.u, self.rng.random(4096)])
        return float(self.u[i])

    def offer(self, i: int):
        """Whether request i's answer is kept, and the index it evicts."""
        u = self.draw(i)
        if len(self.heap) < self.size:
            heapq.heappush(self.heap, (-u, i))
            return True, None
        if u < -self.heap[0][0]:
            return True, heapq.heapreplace(self.heap, (-u, i))[1]
        return False, None


class Records:
    """What the loop saw of each request, in the order it was sent."""

    def __init__(self, sample: Sample | None = None):
        self.due, self.sent, self.done, self.ok = [], [], [], []
        self.kind, self.payload = [], []
        self.sample = sample
        self.kept: dict = {}   # i -> ids of a sampled answer
        self.pending: dict = {}  # i -> Request still in flight

    def add(self, i, kind, lo, hi, k, due, req):
        self.due.append(due)
        self.sent.append(req.t_submit)
        self.done.append(np.nan)
        self.ok.append(False)
        self.kind.append(kind)
        self.payload.append((lo, hi, k))
        self.pending[i] = req

    def collect(self, i) -> bool:
        """Take request i's answer if it came; keep the ids if sampled."""
        req = self.pending.get(i)
        if req is None or not req.done:
            return req is None
        self.done[i] = req.t_done
        # an answer the server itself does not certify exact (a scan that
        # failed comes back empty with a degraded certificate) is no answer
        exact = getattr(req.cert, "certified_exact", True)
        self.ok[i] = req.status == STATUS_OK and bool(exact)
        if self.ok[i] and self.sample is not None:
            keep, evicted = self.sample.offer(i)
            if keep:
                self.kept[i] = np.array(req.ids, dtype=np.int64)
            self.kept.pop(evicted, None)
        req.ids = None
        del self.pending[i]
        return True

    def wait_all(self, until: float) -> None:
        for i in sorted(self.pending):
            req = self.pending[i]
            req.wait(max(until - time.monotonic(), 0.0))
            self.collect(i)

    def not_ok(self, t_end: float) -> int:
        """Requests due in the window that did not come back ``ok`` and
        certified exact: refused, shed, failed or never answered."""
        due = np.asarray(self.due)
        return int((~np.asarray(self.ok, dtype=bool)[due < t_end]).sum())


def _submit(fe, kind, lo, hi, k):
    if kind == 0:
        return fe.submit_window(lo, hi)
    return fe.submit_knn(lo, int(k))


def _span(name):
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


def drive_open(fe, reqs: gen.Requests, offsets: np.ndarray, rec: Records,
               t0: float) -> None:
    """Send request i at ``t0 + offsets[i]``, whatever the answers do."""
    cursor = 0
    for i, off in enumerate(offsets):
        due = t0 + off
        now = time.monotonic()
        if due > now:
            time.sleep(due - now)
        with _span("generator.submit"):
            req = _submit(fe, reqs.kind[i], reqs.lo[i], reqs.hi[i], reqs.k[i])
        rec.add(i, int(reqs.kind[i]), reqs.lo[i], reqs.hi[i], int(reqs.k[i]),
                due, req)
        while cursor <= i and rec.collect(cursor):
            cursor += 1


def drive_closed(fe, stream: gen.RequestStream, outstanding: int,
                 rec: Records, t0: float, t_end: float) -> None:
    """Keep ``outstanding`` requests in flight until ``t_end``: each answer
    sends the next request of the stream at once."""
    flight = collections.deque()
    i = 0

    def send():
        nonlocal i
        kind, lo, hi, k = stream.get(i, time.monotonic() - t0)
        with _span("generator.submit"):
            req = _submit(fe, kind, lo, hi, k)
        rec.add(i, kind, lo, hi, k, req.t_submit, req)
        flight.append(i)
        i += 1

    while len(flight) < outstanding:
        send()
    while flight and time.monotonic() < t_end:
        head = flight[0]
        rec.pending[head].wait(max(t_end - time.monotonic(), 0.0))
        while flight and rec.collect(flight[0]):
            flight.popleft()
            if time.monotonic() < t_end:
                send()


def run_window(server, traffic: dict, points: np.ndarray, seconds: float,
               rng_req, rng_arr, sample: Sample | None = None,
               spans: bool = True, rng_drift=None):
    """Drive a fresh, started ``Frontend`` over ``server`` for ``seconds``;
    a drifting hot spot draws its places from ``rng_drift``.  Returns the
    records, the Frontend's stats and the window's bounds."""
    from repro.serve.frontend import Frontend

    spots = gen.hotspots(traffic, points, rng_drift)
    fe = Frontend(server, queue_bound=int(traffic["queue_bound"]),
                  batch_max=BATCH_MAX).start()
    rec = Records(sample)
    try:
        t0 = time.monotonic() + 0.05
        t_end = t0 + seconds
        with _span("bench.window") if spans else contextlib.nullcontext():
            if traffic["loop"] == "open":
                offsets = gen.arrivals(traffic, seconds, rng_arr)
                reqs = gen.make_requests(traffic, points, len(offsets),
                                         rng_req, offsets, spots)
                drive_open(fe, reqs, offsets, rec, t0)
            elif traffic["loop"] == "closed":
                stream = gen.RequestStream(traffic, points, rng_req, spots)
                while time.monotonic() < t0:
                    time.sleep(t0 - time.monotonic())
                drive_closed(fe, stream, int(traffic["outstanding"]), rec,
                             t0, t_end)
            else:
                raise ValueError(f"unknown loop {traffic['loop']!r}")
            rec.wait_all(t_end + GRACE_S)
    finally:
        fe.stop(drain=False)
    return rec, fe.stats, (t0, t_end)


def warm_up(server, traffic: dict, points: np.ndarray, seed: int,
            clock: CompileClock, seconds: float, config: dict) -> dict:
    """Build every program the traffic reaches before the window: the
    batches :mod:`bench.warmup` plans by the engine's buckets, then passes
    of the cell's own traffic through a ``Frontend`` until one builds
    nothing new (at most ``warmup_passes``).

    Where the configuration's boot changes its server as it serves, no
    request reaches ``server``: a second server, booted alike from the
    same points, takes the passes and is dropped.  The plan is left out
    there, since it is built from the leaf boxes of a table that the
    passes themselves refine."""
    from bench import warmup

    boot = load_boot(boot_name(config))
    if boot.CHANGES_AS_IT_SERVES:
        server, _ = boot.boot(config, points, BATCH_MAX)
        planned = {"planned_batches": 0}
    else:
        dev = server.dev
        planned = warmup.run(server, traffic, points,
                             gen.rng_for(seed, gen.STREAM_WARMUP, 0),
                             np.asarray(dev.leaf_lo)[: dev.n_leaves],
                             np.asarray(dev.leaf_hi)[: dev.n_leaves],
                             BATCH_MAX)
    planned_builds = clock.builds
    passes, before = 0, clock.builds
    for p in range(int(traffic["warmup_passes"])):
        before = clock.builds
        run_window(server, traffic, points, seconds,
                   gen.rng_for(seed, gen.STREAM_WARMUP, 1, p),
                   gen.rng_for(seed, gen.STREAM_WARMUP, 2, p), spans=False,
                   rng_drift=gen.rng_for(seed, gen.STREAM_WARMUP, 3, p))
        passes += 1
        if clock.builds == before:
            break
    if boot.CHANGES_AS_IT_SERVES:
        del server
        gc.collect()  # the second server's tables leave the device now
    return {**planned, "planned_builds": planned_builds, "passes": passes,
            "pass_builds": clock.builds - planned_builds,
            "last_pass_builds": clock.builds - before}


# -- the numbers ----------------------------------------------------------------
def percentile_ms(lat: np.ndarray, q: float) -> float:
    """The q-th percentile, by the nearest rank: a value that some request
    really waited."""
    s = np.sort(lat)
    rank = max(int(np.ceil(q / 100.0 * len(s))) - 1, 0)
    return float(s[rank] * 1e3)


def end_to_end(rec: Records, window, seconds: float, setup_s: float,
               build: dict) -> tuple[dict, int, int]:
    t0, t_end = window
    due = np.asarray(rec.due)
    done = np.asarray(rec.done, dtype=np.float64)
    ok = np.asarray(rec.ok, dtype=bool)
    in_window = due < t_end
    # a request that failed or never came misses every limit: it counts as
    # waiting longer than any answer could
    lat = np.where(ok, done - due, seconds + GRACE_S)[in_window]
    attempted = int(in_window.sum())
    failed = int((~ok[in_window]).sum())
    answered = int((ok & (done <= t_end)).sum())
    values = {
        "p99_ms": percentile_ms(lat, 99),
        "p50_ms": percentile_ms(lat, 50),
        "qps": answered / seconds,
        "load_s": build["bulk_load_s"] + build["upload_s"],
        "setup_s": setup_s,
    }
    return values, attempted, failed


@dataclasses.dataclass
class Context:
    """What a per-layer metric's ``read(ctx)`` may read."""

    cell: Cell
    seconds: float
    records: Records
    window: tuple
    frontend: object
    engine_calls: list
    build: dict
    compiles_in_window: int
    trace: dict | None
    leaf_lo: np.ndarray
    leaf_hi: np.ndarray
    leaf_size: int
    device_kind: str
    reference: object = None
    # the server's counters (``server_stats``) as the window started, and
    # once its last answer came
    stats_start: dict = dataclasses.field(default_factory=dict)
    stats_end: dict = dataclasses.field(default_factory=dict)

    def stat_change(self, name: str):
        """How much the server's counter ``name`` grew over the window;
        None where the server has no such counter."""
        if name not in self.stats_start or name not in self.stats_end:
            return None
        return self.stats_end[name] - self.stats_start[name]

    def calls(self, kind: str) -> int:
        return sum(1 for c in self.engine_calls if c[0] == kind)

    def stage_ms(self, modules, kind: str):
        """Device ms per ``kind`` microbatch of the jitted ``modules``."""
        if self.trace is None or not self.calls(kind):
            return None
        secs = sum(self.trace["modules"].get(m, (0.0, 0))[0] for m in modules)
        return 1e3 * secs / self.calls(kind) if secs else None

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the ops that run the Pallas kernel ``kernel``
        (custom calls named ``<kernel>.<n>``)."""
        if self.trace is None:
            return 0.0
        return sum(v[0] for key, v in self.trace["ops"].items()
                   if key.split(":", 1)[1].rsplit(".", 1)[0] == kernel)

    def requests(self, kind: int):
        """The window's requests of one kind as arrays (lo, hi, k)."""
        sel = [p for p, kd in zip(self.records.payload, self.records.kind)
               if kd == kind]
        if not sel:
            return None
        lo = np.stack([p[0] for p in sel])
        hi = np.stack([p[1] for p in sel])
        return lo, hi, np.array([p[2] for p in sel])


def slowest_text(rec: Records, window, n: int = 8) -> str:
    """The ``n`` slowest requests of the window: when each was due (seconds
    into the window) and how long it took (ms), slowest first."""
    t0, _ = window
    due = np.asarray(rec.due)
    lat = np.asarray(rec.done, dtype=np.float64) - due
    lat = np.where(np.asarray(rec.ok, dtype=bool), lat, np.inf)
    worst = np.argsort(-lat)[:n]
    return " ".join(f"{due[i] - t0:.3f}s:{lat[i] * 1e3:.1f}ms" for i in worst)


def checked_requests(rec: Records) -> list:
    """``(kind, lo, hi, k, ids)`` of each sampled answer, in request order."""
    return [(rec.kind[i], *rec.payload[i], rec.kept[i])
            for i in sorted(rec.kept)]


def sample_for(traffic: dict, seed: int) -> Sample:
    """The ``check`` answers the run compares with the reference."""
    return Sample(int(traffic["check"]), gen.rng_for(seed, gen.STREAM_CHECK))

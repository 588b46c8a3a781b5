"""A configuration, its boot, a traffic mix and a per-layer metric are
found by name: adding them as files and manifest entries is all a new cell
needs."""
import json
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as h  # noqa: E402
from bench.tests import mixes  # noqa: E402


def _checkout(tmp_path):
    """A copy of the benchmark's files, as a later PR would find them."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return {p.relative_to(tmp_path): p.read_bytes()
            for p in tmp_path.rglob("*") if p.is_file()}


# the new files of each case: a configuration, a mix and the cell's name
NEW_CELLS = {
    "unif-1k.tiny": (
        {"generator": "osm_like", "n_points": 1000, "dim": 2,
         "data_seed": 3, "buffer_fraction": 0.05},
        {"loop": "open", "rate": 10.0, "queue_bound": 100,
         "mix": [{"kind": "window", "share": 1.0, "half_width": [0.1, 0.1],
                  "centre": {"from": "data"}}],
         "check": 5, "warmup_seconds": 0.1, "warmup_passes": 1}),
    # the next deployment: 5-D trips, windows and k-NN mixed
    "nycyt5-1k.mixed": (
        {"generator": "nycyt_like", "n_points": 1000, "dim": 5,
         "data_seed": 0, "buffer_fraction": 0.05},
        mixes.MIXED),
    # the adaptive server: AMBI's cold start under a hot spot that moves
    "osm-1k.ambi-drift": (
        {"generator": "osm_like", "n_points": 1000, "dim": 2,
         "data_seed": 0, "buffer_fraction": 0.05, "boot": "ambi"},
        dict(mixes.drifting(mixes.window_focused(), 0.2), rate=20.0,
             warmup_seconds=0.2, check=64)),
}
# a reader of the server's counters, as a cell of the adaptive boot adds
GRAFTS = "def read(ctx):\n    g = ctx.stat_change(\"grafts\")\n" \
         "    return None if g is None else g / ctx.seconds\n"


@pytest.mark.parametrize("cell_name", sorted(NEW_CELLS))
def test_new_files_are_found_by_name(tmp_path, cell_name):
    before = _checkout(tmp_path)
    bench = tmp_path / "bench"
    config_name, traffic_name = cell_name.split(".")
    config, traffic = NEW_CELLS[cell_name]
    # the new files: a configuration, a mix and a metric reader
    (bench / "configs" / f"{config_name}.json").write_text(json.dumps(config))
    (bench / "traffic" / f"{traffic_name}.json").write_text(
        json.dumps(traffic))
    (bench / "metrics" / "tiny.answered.py").write_text(
        "def read(ctx):\n    return float(sum(ctx.records.ok))\n")
    (bench / "metrics" / "tiny.grafts_per_s.py").write_text(GRAFTS)
    # ... and entries in the manifest
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": config_name, "source": "x",
                                "file": f"bench/configs/{config_name}.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": cell_name, "config": config_name,
                                  "traffic": traffic_name, "chips": 1,
                                  "why": "test"})
    manifest["per_layer"].append({"name": "tiny.answered", "unit": "count",
                                  "better": "higher", "source": "host_clock",
                                  "layer": "test", "moves": "qps",
                                  "workloads": [cell_name]})
    manifest["per_layer"].append({"name": "tiny.grafts_per_s",
                                  "unit": "grafts/s", "better": "higher",
                                  "source": "program_counter",
                                  "layer": "test", "moves": "p50_ms",
                                  "workloads": [cell_name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = h.resolve_cell(h.load_manifest(tmp_path), cell_name, tmp_path)
    assert cell.config == config
    assert cell.traffic == traffic
    assert "tiny.answered" in [m["name"] for m in cell.per_layer]
    read = h.load_reader("tiny.answered", bench / "metrics")

    class Ctx:
        class records:
            ok = [True, False, True]

    assert read(Ctx) == 2.0
    grafts = h.load_reader("tiny.grafts_per_s", bench / "metrics")
    assert grafts(_ctx({"grafts": 2}, {"grafts": 12})) == 5.0
    assert grafts(_ctx({}, {})) is None
    if h.boot_name(config) == "ambi":
        import jax

        from bench import run

        result, numbers = run.run_cell(cell, 2**31 + 5, 0.6, False,
                                       jax.devices())
        assert result["correct"] and result["attempted"] > 0, numbers
    # no file that was there before changed
    for rel, data in before.items():
        if rel.name != "BENCHMARK.json":
            assert (tmp_path / rel).read_bytes() == data, rel


def _ctx(stats_start, stats_end):
    return h.Context(cell=None, seconds=2.0, records=None, window=(0, 2),
                     frontend=None, engine_calls=[], build={},
                     compiles_in_window=0, trace=None, leaf_lo=None,
                     leaf_hi=None, leaf_size=0, device_kind="",
                     stats_start=stats_start, stats_end=stats_end)


def test_an_unknown_boot_is_refused_before_any_run(tmp_path):
    _checkout(tmp_path)
    config = dict(NEW_CELLS["osm-1k.ambi-drift"][0], boot="nowhere")
    (tmp_path / "bench" / "configs" / "osm-1k.json").write_text(
        json.dumps(config))
    manifest = h.load_manifest(tmp_path)
    manifest["configs"].append({"name": "osm-1k", "source": "x",
                                "file": "bench/configs/osm-1k.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "osm-1k.window-focused",
                                  "config": "osm-1k",
                                  "traffic": "window-focused", "chips": 1,
                                  "why": "test"})
    with pytest.raises(h.SetupError, match="no boot 'nowhere'"):
        h.resolve_cell(manifest, "osm-1k.window-focused", tmp_path)


def test_every_cell_of_the_manifest_resolves():
    manifest = h.load_manifest(ROOT)
    for w in manifest["workloads"]:
        cell = h.resolve_cell(manifest, w["name"], ROOT)
        assert cell.traffic["loop"] in ("open", "closed")
        for m in cell.per_layer:
            assert callable(h.load_reader(m["name"]))


def test_run_refuses_without_a_chip():
    """Without a TPU the run exits non-zero before any measurement and
    prints no result."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "osm-10m.window-focused", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_run_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and bench/ runs nothing."""
    _checkout(tmp_path)
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "osm-10m.window-focused", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert out.returncode != 0 and out.stdout == ""

"""A configuration, a traffic mix and a per-layer metric are found by
name: adding them as files and manifest entries is all a new cell needs."""
import json
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as h  # noqa: E402


def _checkout(tmp_path):
    """A copy of the benchmark's files, as a later PR would find them."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return {p.relative_to(tmp_path): p.read_bytes()
            for p in tmp_path.rglob("*") if p.is_file()}


def test_new_files_are_found_by_name(tmp_path):
    before = _checkout(tmp_path)
    bench = tmp_path / "bench"
    # the new files: a configuration, a mix and a metric reader
    (bench / "configs" / "unif-1k.json").write_text(json.dumps(
        {"generator": "osm_like", "n_points": 1000, "dim": 2,
         "data_seed": 3, "buffer_fraction": 0.05}))
    (bench / "traffic" / "tiny-windows.json").write_text(json.dumps(
        {"loop": "open", "rate": 10.0, "queue_bound": 100,
         "mix": [{"kind": "window", "share": 1.0, "half_width": [0.1, 0.1],
                  "centre": {"from": "data"}}],
         "check": 5, "warmup_seconds": 0.1, "warmup_passes": 1}))
    (bench / "metrics" / "tiny.answered.py").write_text(
        "def read(ctx):\n    return float(sum(ctx.records.ok))\n")
    # ... and entries in the manifest
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": "unif-1k", "source": "x",
                                "file": "bench/configs/unif-1k.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "unif-1k.tiny", "config": "unif-1k",
                                  "traffic": "tiny-windows", "chips": 1,
                                  "why": "test"})
    manifest["per_layer"].append({"name": "tiny.answered", "unit": "count",
                                  "better": "higher", "source": "host_clock",
                                  "layer": "test", "moves": "qps",
                                  "workloads": ["unif-1k.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = h.resolve_cell(h.load_manifest(tmp_path), "unif-1k.tiny", tmp_path)
    assert cell.config["n_points"] == 1000
    assert cell.traffic["mix"][0]["half_width"] == [0.1, 0.1]
    assert "tiny.answered" in [m["name"] for m in cell.per_layer]
    read = h.load_reader("tiny.answered", bench / "metrics")

    class Ctx:
        class records:
            ok = [True, False, True]

    assert read(Ctx) == 2.0
    # no file that was there before changed
    for rel, data in before.items():
        if rel.name != "BENCHMARK.json":
            assert (tmp_path / rel).read_bytes() == data, rel


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

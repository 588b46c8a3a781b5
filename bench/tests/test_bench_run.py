"""The rest of a run on the CPU at a small size: set-up, the window
through ``Frontend``, the reference, and ``correct``.  The chip gate is
skipped (these tests hand the run the CPU device); the control and the
faults below must come out not correct."""
import copy
import json
import pathlib
import sys
import time

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import generator as gen  # noqa: E402
from bench import harness as h  # noqa: E402
from bench import reference  # noqa: E402
from bench import run  # noqa: E402
from bench.datasets import make_points  # noqa: E402
from bench.tests import mixes  # noqa: E402

N = 20_000
SEED = 2**31 + 99

# cells at N points: the manifest's own, and the mixes kept for later cells
MIXES = {
    "osm-10m.window-focused": ("osm-10m", mixes.window_focused),
    "osm-10m.knn-near": ("osm-10m", mixes.knn_near),
    "osm-25m.window-focused": (
        "osm-25m", lambda: mixes.shortened("window-focused-25m")),
    "nycyt5.mixed": ("nycyt5", lambda: mixes.MIXED),
    "osm-10m.window-focused.closed": (
        "osm-10m", lambda: mixes.closed(mixes.window_focused())),
    # the hot spot jumping twice in a one-second window, over the static
    # FMBI index and over the AMBI boot
    "osm-10m.window-drift": (
        "osm-10m", lambda: mixes.drifting(mixes.window_focused(),
                                          DRIFT_EVERY_S)),
    "osm-ambi.window-drift": (
        "osm-ambi", lambda: mixes.drifting(mixes.window_focused(),
                                           DRIFT_EVERY_S)),
}
NYCYT5 = {"generator": "nycyt_like", "dim": 5, "data_seed": 0,
          "buffer_fraction": 0.05}
DRIFT_EVERY_S = 0.4


def small(cell_name):
    """The cell at N points, its traffic shortened for the CPU."""
    config_name, make_traffic = MIXES[cell_name]
    manifest = h.load_manifest(ROOT)
    if config_name == "nycyt5":
        config = dict(NYCYT5)
    elif config_name == "osm-ambi":
        config = dict(json.loads((ROOT / "bench" / "configs" /
                                  "osm-10m.json").read_text()), boot="ambi")
    else:
        config = json.loads((ROOT / "bench" / "configs" /
                             f"{config_name}.json").read_text())
    return h.Cell(cell_name, 1, config_name, dict(config, n_points=N),
                  "", copy.deepcopy(make_traffic()), manifest["end_to_end"],
                  [])


def one_run(cell, seconds=1.0, control=False):
    return run.run_cell(cell, SEED, seconds, False, jax.devices(),
                        control=control, t_start=time.monotonic(),
                        limits=reference.LIMITS)


@pytest.mark.parametrize("cell_name", sorted(MIXES))
def test_sound_run_is_correct_and_the_control_is_not(cell_name):
    cell = small(cell_name)
    result, numbers = one_run(cell, control=True)
    assert result["correct"], numbers
    assert result["attempted"] > 0 and result["failed"] == 0
    assert numbers["not_ok"]["value"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "control"
    assert not reference.passes(result["control"])


def _alter_windows(monkeypatch):
    from repro.core import queries_jax

    real = queries_jax.window_query_batch_jax

    def altered(*a, **kw):
        out = real(*a, **kw)
        res = out[0] if isinstance(out, tuple) else out
        for i, ids in enumerate(res):
            if len(ids):
                res[i] = ids[:-1]  # one id lost where the answer is made
                break
        return out

    monkeypatch.setattr(queries_jax, "window_query_batch_jax", altered)


def _alter_knn(monkeypatch):
    from repro.core import queries_jax

    real = queries_jax.knn_query_batch_jax

    def altered(*a, **kw):
        out = real(*a, **kw)
        res = out[0] if isinstance(out, tuple) else out
        res[0] = np.concatenate([res[0][:-1], [(res[0][-1] + 7) % N]])
        return out

    monkeypatch.setattr(queries_jax, "knn_query_batch_jax", altered)


def _raise_windows(monkeypatch):
    """Every window scan raises once set-up is over (a set-up that fails
    ends the run with no result, which is refused anyway)."""
    from repro.core import queries_jax

    def broken(*a, **kw):
        raise RuntimeError("planted: the window scan fails")

    real = h.warm_up

    def warm_then_break(*a, **kw):
        out = real(*a, **kw)
        monkeypatch.setattr(queries_jax, "window_query_batch_jax", broken)
        return out

    monkeypatch.setattr(h, "warm_up", warm_then_break)


def _drop_half(monkeypatch):
    from repro.core import queries_jax

    real = queries_jax.window_query_batch_jax

    def half(dev, los, his, **kw):
        out = real(dev, los, his, **kw)
        res = out[0] if isinstance(out, tuple) else out
        for i in range(len(res) // 2, len(res)):
            res[i] = res[i][:0]  # the batch's second half answered empty
        return out

    monkeypatch.setattr(queries_jax, "window_query_batch_jax", half)


def _drop_half_knn(monkeypatch):
    from repro.core import queries_jax

    real = queries_jax.knn_query_batch_jax

    def half(dev, qs, k, **kw):
        out = real(dev, qs, k, **kw)
        res = out[0] if isinstance(out, tuple) else out
        for i in range(len(res) // 2, len(res)):
            res[i] = res[i][:0]  # the batch's second half answered empty
        return out

    monkeypatch.setattr(queries_jax, "knn_query_batch_jax", half)


@pytest.mark.parametrize("cell_name,alter", [
    ("osm-10m.window-focused", _alter_windows),
    ("osm-10m.knn-near", _alter_knn),
    ("osm-10m.knn-near", _drop_half_knn),
    ("osm-25m.window-focused", _alter_windows),
    ("osm-25m.window-focused", _drop_half),
    ("nycyt5.mixed", _alter_windows),
    ("nycyt5.mixed", _alter_knn),
    ("osm-10m.window-focused", _raise_windows),
    ("osm-10m.window-focused", _drop_half),
    ("osm-ambi.window-drift", _alter_windows),
    ("osm-ambi.window-drift", _drop_half),
])
def test_an_answer_altered_where_it_is_made_is_not_correct(
        monkeypatch, cell_name, alter):
    alter(monkeypatch)
    cell = small(cell_name)
    cell.traffic["check"] = 10**6  # compare every answer
    result, numbers = one_run(cell)
    assert not result["correct"], numbers


def test_an_adaptive_boot_serves_the_window_from_its_cold_start(
        monkeypatch):
    """The AMBI boot: no warm-up request reaches the server the window
    serves (its counters are 0 as the window starts), a second server
    takes the warm-up, and in the window queries reach cold space and
    are grafted while the hot spot moves."""
    calls = []
    real = h.run_window

    def spy(server, traffic, points, seconds, *a, **kw):
        inner = getattr(server, "_server", server)
        before = h.server_stats(inner)
        out = real(server, traffic, points, seconds, *a, **kw)
        calls.append((inner, before, h.server_stats(inner), out))
        return out

    monkeypatch.setattr(h, "run_window", spy)
    result, numbers = one_run(small("osm-ambi.window-drift"))
    assert result["correct"], numbers
    *warm, (served, start, end, (rec, _, (t0, _))) = calls
    assert warm and all(w[0] is not served for w in warm)
    assert warm[-1][2]["queries"] > 0
    assert all(v == 0 for k, v in start.items() if k != "shards"), start
    assert end["cold_queries"] > 0 and end["grafts"] > 0
    # the window's requests follow at least two places of the hot spot
    cell = small("osm-ambi.window-drift")
    spot = gen.hotspots(cell.traffic, make_points(cell.config),
                        gen.rng_for(SEED, gen.STREAM_WINDOW, 2))[0]
    due = np.asarray(rec.due) - t0
    centres = spot.at(due)
    assert len({tuple(c) for c in centres}) >= 2
    mid = np.array([(lo + hi) / 2 for lo, hi, _ in rec.payload])
    assert np.all(np.abs(mid - centres) <= 0.03 + 1e-6)


def test_a_failing_scan_is_not_correct_even_with_nothing_sampled(
        monkeypatch):
    """Every answer lost: no window is compared, and ``not_ok`` alone
    makes the run not correct."""
    _raise_windows(monkeypatch)
    cell = small("osm-10m.window-focused")
    result, numbers = one_run(cell)
    assert "window_wrong_ids" not in numbers
    assert numbers["not_ok"]["value"] == result["attempted"] > 0
    assert result["failed"] == result["attempted"]
    assert not result["correct"]


@pytest.mark.parametrize("limited", [False, True],
                         ids=["no-limit-refused", "knn-resolves"])
def test_a_cell_resolves_only_where_its_kinds_have_limits(
        tmp_path, monkeypatch, limited):
    """A k-NN cell resolves now that ``knn_gap`` has its limit; a mix
    whose kind is compared by a number without a limit is still refused."""
    import shutil

    if not limited:
        monkeypatch.delitem(reference.LIMITS, "knn_gap")
    shutil.copytree(ROOT / "bench" / "configs", tmp_path / "bench" / "configs")
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "traffic" / "knn.json").write_text(
        json.dumps(mixes.knn_near()))
    manifest = h.load_manifest(ROOT)
    manifest["workloads"] = [{"name": "osm-10m.knn", "config": "osm-10m",
                              "traffic": "knn", "chips": 1, "why": "test"}]
    if limited:
        cell = h.resolve_cell(manifest, "osm-10m.knn", tmp_path)
        assert cell.traffic["mix"][0]["kind"] == "knn"
    else:
        with pytest.raises(h.SetupError, match="knn_gap"):
            h.resolve_cell(manifest, "osm-10m.knn", tmp_path)


def test_points_are_float32_exact_and_fixed():
    cfg = dict(small("nycyt5.mixed").config, n_points=1000)
    a, b = make_points(cfg), make_points(cfg)
    assert np.array_equal(a, b)
    assert np.array_equal(a, a.astype(np.float32).astype(np.float64))

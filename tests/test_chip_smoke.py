"""``chip_smoke.py``'s phases on the CPU at a small size.

The script gates on a TPU in ``main()`` only, so its phases run here with
the Pallas kernels forced on (``use_kernel=True``), which off a TPU means
interpret mode.  The four-chip phase runs in a subprocess on four virtual
CPU devices, so the device count never leaks into this process.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402

N = 100_000


@pytest.fixture(scope="module")
def index():
    idx, build_s = cs.build_index(N, seed=0)
    assert build_s > 0 and len(idx.points) == N
    return idx


@pytest.fixture(scope="module")
def server(index):
    srv, boot_s = cs.boot_server(index, use_kernel=True)
    assert boot_s > 0 and srv.use_kernel
    return srv


# -- (a) the device gate ---------------------------------------------------
def test_device_gate_refuses_a_cpu():
    with pytest.raises(cs.SmokeFailure, match="platform 'cpu'"):
        cs.device_gate()


@pytest.mark.parametrize("value", ["1", "true", "yes"])
def test_device_gate_refuses_interpret_mode(monkeypatch, value):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
    with pytest.raises(cs.SmokeFailure, match="REPRO_PALLAS_INTERPRET"):
        cs.device_gate()


def test_main_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "'cpu'" in out.err


def test_script_alone_exits_nonzero(tmp_path):
    """Copied out of the checkout, the script cannot import the program:
    it fails before printing anything."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


# -- (b) build and boot ----------------------------------------------------
def test_leaf_table_bytes(server):
    lt = cs.leaf_table_bytes(server.dev)
    n_l, s, d = lt["shape"]
    assert d == 2 and n_l == server.dev.n_leaves
    assert lt["logical_bytes"] == n_l * s * d * 4
    assert lt["device_bytes"] >= lt["logical_bytes"]
    assert lt["ratio"] == lt["device_bytes"] / lt["logical_bytes"]


def test_interpreted_stages_hold_no_kernel(server):
    """Off a TPU the kernels are interpreted, so the check that the chip
    run makes must come out false here."""
    stages = cs.compiled_stages(server)
    assert sorted(stages) == ["frontier", "knn_round", "window_scan"]
    for st in stages.values():
        assert st["kernel"] is False and st["temp_bytes"] >= 0


def test_first_batches_answer(server):
    assert cs.first_batches(server, np.random.default_rng(5)) > 0


def test_compile_clock_counts_backend_compiles():
    import jax

    with cs.CompileClock() as clock:
        jax.jit(lambda x: x * 3.0 + 17.0)(np.arange(11.0)).block_until_ready()
    assert clock.count >= 1 and clock.seconds > 0.0
    after = clock.count
    jax.jit(lambda x: x * 5.0 - 2.0)(np.arange(13.0)).block_until_ready()
    assert clock.count == after  # uninstalled on exit


# -- (c) + (d) serve through the frontend, then parity ----------------------
def test_serve_requests_and_parity(index, server):
    (los, his, wres), (qs, kres), stats = cs.serve_requests(
        server, n_windows=96, n_knn=32, seed=0
    )
    assert len(wres) == 96 and len(kres) == 32
    assert stats.completed == 128 and stats.dropped == 0
    assert cs.window_parity(index, los[:32], his[:32], wres[:32]) > 0
    assert 0 <= cs.knn_parity(index, qs, kres) <= 32
    assert all(len(r) == cs.K for r in kres)


def test_serve_requests_fails_on_a_shed_request(server, monkeypatch):
    from repro.serve.resilience import RetryPolicy

    def broken(*_a, **_kw):
        raise RuntimeError("device lost")

    monkeypatch.setattr(server, "retry", RetryPolicy(max_attempts=1))
    monkeypatch.setattr(server, "window", broken)
    with pytest.raises(cs.SmokeFailure, match=r"shed \(dispatch failed"):
        cs.serve_requests(server, n_windows=6, n_knn=2, seed=1)


def test_window_parity_catches_a_missing_id(index):
    rng = np.random.default_rng(3)
    los, his = cs.hotspot_windows(4, cs.hotspot_centre(index.points, rng), rng)
    from repro.core import window_query_batch

    ref, _ = window_query_batch(index, los, his)
    got = [r.copy() for r in ref]
    i = next(j for j, r in enumerate(got) if len(r))
    got[i] = got[i][1:]
    with pytest.raises(cs.SmokeFailure, match=f"window {i}"):
        cs.window_parity(index, los, his, got)


def test_knn_matches_follows_the_tie_rule():
    pts = np.array([[0.0, 0.0], [1.0, 0.0], [0.0, 1.0], [3.0, 0.0]])
    q = np.zeros(2)
    assert cs.knn_matches(pts, q, [0, 1, 2], [0, 1, 2])
    # points 1 and 2 tie: either order, either id at the k-th distance
    assert cs.knn_matches(pts, q, [0, 2, 1], [0, 1, 2])
    assert cs.knn_matches(pts, q, [0, 2], [0, 1])
    # a farther point is never an acceptable substitute
    assert not cs.knn_matches(pts, q, [0, 3], [0, 1])
    assert not cs.knn_matches(pts, q, [1, 0], [0, 3])
    # below the k-th distance the ids must agree, even among ties
    assert not cs.knn_matches(pts, q, [1, 3], [2, 3])
    assert not cs.knn_matches(pts, q, [0], [0, 1])


# -- (e) adaptive serving --------------------------------------------------
def test_adaptive_phase_second_pass_is_device_only(index):
    out = cs.adaptive_phase(index.points, index, seed=0, use_kernel=True)
    assert out["use_kernel"] is True
    assert out["cold_queries"] > 0 and out["grafts"] > 0
    assert out["windows"] == cs.MICROBATCH and out["ids_checked"] > 0


# -- four chips, on four virtual CPU devices ---------------------------------
FOUR_CHIP_SCRIPT = r"""
import json, sys
sys.path[:0] = ["src", "."]
import jax
if len(jax.devices()) < 4:
    print(f"FOUR-SKIP: only {len(jax.devices())} devices"); sys.exit(0)
import chip_smoke as cs
idx, _ = cs.build_index(60_000, seed=0)
out = cs.four_chip_phase(idx, seed=0, n_queries=8)
print("FOUR-OK " + json.dumps(out))
"""


def test_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run(
        [sys.executable, "-c", FOUR_CHIP_SCRIPT], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    if "FOUR-SKIP" in res.stdout:
        pytest.skip(res.stdout.strip())
    assert res.returncode == 0, res.stdout + res.stderr
    line = next(x for x in res.stdout.splitlines() if x.startswith("FOUR-OK"))
    out = json.loads(line.split(" ", 1)[1])
    assert out["knn_queries"] == 8 and out["windows"] == 8
    assert out["window_points"] > 0
    assert all(ids == [0, 1, 2, 3] for ids in out["device_sets"].values())
    assert "[four-chip] array=leaf_pts" in res.stdout

"""Roofline machinery: HLO parsing (incl. while-loop multipliers) and the
analytic FLOPs model cross-checked against XLA cost_analysis."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import roofline
from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import model as M
from repro.launch.mesh import make_mesh
from repro.models.sharding import MeshAxes


def test_shape_bytes():
    assert roofline._shape_bytes("bf16[2,3,4]{2,1,0}") == 48
    assert roofline._shape_bytes("f32[10]") == 40
    assert roofline._shape_bytes("(f32[4], bf16[8])") == 32
    assert roofline._shape_bytes("pred[]") == 1  # scalar: one byte


def test_while_loop_multiplier_recovered():
    """Collectives inside a scanned body must be multiplied by trip count."""
    import subprocess
    import sys

    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro import roofline
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("m",))
L, D = 7, 64
def f(ws, x):
    def body(c, w):
        y = c @ w                      # sharded matmul -> all-reduce/gather
        return y, None
    out, _ = jax.lax.scan(body, x, ws)
    return out.sum()
ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32,
                          sharding=NamedSharding(mesh, P(None, "m", None)))
x = jax.ShapeDtypeStruct((8, D), jnp.float32,
                         sharding=NamedSharding(mesh, P(None, "m")))
with jax.set_mesh(mesh):
    c = jax.jit(f).lower(ws, x).compile()
res = roofline.parse_collectives(c.as_text())
counts = sum(res["counts"].values())
assert counts > 0, "no collectives found"
per = res["total_bytes"] / max(counts, 1)
# bytes must reflect the x7 trip count: far larger than one op's payload
assert res["total_bytes"] >= 7 * 8 * 16 * 4, res
print("MULT-OK", res["total_bytes"])
"""
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=str(__import__("pathlib").Path(__file__).resolve().parents[1]),
        timeout=300,
    )
    assert "MULT-OK" in res.stdout, res.stdout + res.stderr


def _tiny_cfg(**kw):
    base = dict(
        name="tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab=128, dtype="float32", chunk_q=32,
    )
    base.update(kw)
    return ModelConfig(**base)


def test_analytic_flops_close_to_xla_forward():
    """Forward-only FLOPs: analytic model within 25% of XLA's count on a
    small dense config (unrolled enough that nothing hides in while loops:
    single q-chunk, single loss chunk)."""
    cfg = _tiny_cfg()
    shape = ShapeConfig("t", seq_len=32, global_batch=4, kind="prefill")
    mesh = make_mesh((1, 1), ("data", "model"))
    axes = MeshAxes()
    params = M.abstract_params(cfg, mesh, jnp.float32)
    inputs = M.input_specs(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        c = jax.jit(lambda p, b: M.prefill(p, cfg, b, axes)).lower(
            params, inputs
        ).compile()
    xla = c.cost_analysis()["flops"]
    # scan over 2 layers counted once by XLA -> add one body back
    body = xla  # lower 1-layer variant for the body estimate
    cfg1 = dataclasses.replace(cfg, n_layers=1)
    params1 = M.abstract_params(cfg1, mesh, jnp.float32)
    with jax.set_mesh(mesh):
        c1 = jax.jit(lambda p, b: M.prefill(p, cfg1, b, axes)).lower(
            params1, inputs
        ).compile()
    xla1 = c1.cost_analysis()["flops"]
    per_layer = xla - xla1 if xla > xla1 else 0.0
    xla_full = xla1 + per_layer * cfg.n_layers  # body-once corrected
    ana = roofline.analytic_flops(cfg, shape)["fwd_flops"]
    # prefill computes logits on the last position only; analytic model
    # includes the same head term
    assert 0.5 < ana / max(xla_full, 1) < 2.0, (ana, xla_full)


def test_roofline_terms_and_dominance():
    t = roofline.roofline_terms(
        flops=1e15, hbm_bytes=1e12, coll_bytes=1e11, chips=256,
        device_kind="TPU v5 lite",
    )
    assert t["dominant"] == "compute_s"
    assert 0 < t["roofline_fraction"] <= 1.0
    t2 = roofline.roofline_terms(
        flops=1e12, hbm_bytes=1e14, coll_bytes=1e11, chips=256,
        device_kind="TPU v5 lite",
    )
    assert t2["dominant"] == "memory_s"


def test_chip_peaks_keyed_by_device_kind():
    v5e = roofline.chip_peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    r = roofline.kernel_roofline(819e9, 2.0, "TPU v5 lite")
    assert r["peak_fraction"] == pytest.approx(0.5)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_chip_peaks_unknown_device_kind_raises(kind):
    """A chip with no published peaks in the table is an error, not a
    silent fallback to another chip's roof."""
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.chip_peaks(kind)
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.kernel_roofline(1e9, 1.0, kind)
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.roofline_terms(1e12, 1e12, 0.0, 1, kind)


def test_probe_extrapolation_linear():
    probe = {
        "blocks1": {"flops": 130.0, "bytes_accessed": 1300.0,
                    "collective_bytes": 13.0},
        "blocks2": {"flops": 230.0, "bytes_accessed": 2300.0,
                    "collective_bytes": 23.0},
    }
    out = roofline.probe_extrapolate(probe, n_blocks=10)
    assert out["flops"] == pytest.approx(30.0 + 100.0 * 10)
    assert out["collective_bytes"] == pytest.approx(3.0 + 10.0 * 10)

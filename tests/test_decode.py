"""Decode == train-forward consistency per family (the serving contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.sharding import MeshAxes

B, S, TAIL = 2, 32, 4


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _grow(cache, s0):
    def f(x):
        if x.ndim >= 3 and x.shape[2] == s0:
            pad = jnp.zeros(x.shape[:2] + (TAIL,) + x.shape[3:], x.dtype)
            return jnp.concatenate([x, pad], axis=2)
        if x.ndim >= 2 and x.shape[1] == s0:
            pad = jnp.zeros((x.shape[0], TAIL) + x.shape[2:], x.dtype)
            return jnp.concatenate([x, pad], axis=1)
        return x

    return jax.tree.map(f, cache)


def _check(cfg, mesh, tol=2e-3):
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    params = M.init_params(cfg, jax.random.key(1), jnp.float32)
    axes = MeshAxes()
    with jax.set_mesh(mesh):
        lg_full, _ = M.forward(params, cfg, {"tokens": toks}, axes,
                               mode="train")
        s0 = S - TAIL
        lg_pre, cache = M.prefill(params, cfg, {"tokens": toks[:, :s0]}, axes)
        cache = _grow(cache, s0)
        errs = [float(jnp.max(jnp.abs(lg_pre[:, -1] - lg_full[:, s0 - 1])))]
        for t in range(s0, S):
            lg_t, cache = M.decode_step(
                params, cfg, toks[:, t : t + 1], cache,
                jnp.full((B,), t, jnp.int32), axes,
            )
            errs.append(float(jnp.max(jnp.abs(lg_t[:, 0] - lg_full[:, t]))))
    assert max(errs) < tol, (cfg.name, errs)


def test_dense_gqa_qknorm(mesh):
    _check(ModelConfig(
        name="t-dense", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=100, qk_norm=True, dtype="float32",
        chunk_q=16,
    ), mesh)


def test_local_global_ring_cache(mesh):
    _check(ModelConfig(
        name="t-gemma", family="dense", n_layers=6, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=100, local_window=8,
        local_per_global=2, dtype="float32", chunk_q=16,
    ), mesh)


def test_rwkv_state_decode(mesh):
    _check(ModelConfig(
        name="t-rwkv", family="rwkv", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=4, d_ff=128, vocab=100, head_dim=16, rwkv_head_dim=16,
        dtype="float32", la_chunk=4,
    ), mesh)


def test_hybrid_mamba_attn_moe(mesh):
    _check(ModelConfig(
        name="t-jamba", family="hybrid", n_layers=8, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=100, n_experts=4, moe_top_k=2,
        moe_dff=128, moe_every=2, attn_every=4, mamba_d_state=8,
        mamba_head_dim=16, dtype="float32", la_chunk=4, chunk_q=16,
        capacity_factor=8.0,  # no capacity drops: decode must equal train
    ), mesh)


def test_encdec_decode_with_cross_cache(mesh):
    cfg = ModelConfig(
        name="t-encdec", family="encdec", n_layers=2, encoder_layers=2,
        d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab=100,
        dtype="float32", chunk_q=16, frontend="audio_stub",
    )
    rng = np.random.default_rng(3)
    frames = jnp.asarray(rng.normal(0, 1, (B, S, 64)), jnp.float32)
    dec = jnp.asarray(rng.integers(0, 100, (B, 16)), jnp.int32)
    params = M.init_params(cfg, jax.random.key(2), jnp.float32)
    axes = MeshAxes()
    with jax.set_mesh(mesh):
        lg_full, _ = M.forward(
            params, cfg, {"frames": frames, "tokens": dec}, axes,
            mode="train",
        )
        s0 = 12
        lg_pre, cache = M.prefill(
            params, cfg, {"frames": frames, "tokens": dec[:, :s0]}, axes
        )
        cache = _grow(cache, s0)
        errs = [float(jnp.max(jnp.abs(lg_pre[:, -1] - lg_full[:, s0 - 1])))]
        for t in range(s0, 16):
            lg_t, cache = M.decode_step(
                params, cfg, dec[:, t : t + 1], cache,
                jnp.full((B,), t, jnp.int32), axes,
            )
            errs.append(float(jnp.max(jnp.abs(lg_t[:, 0] - lg_full[:, t]))))
    assert max(errs) < 2e-3, errs

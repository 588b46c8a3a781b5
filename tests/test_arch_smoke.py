"""Per-architecture smoke tests (deliverable f): REDUCED config of the same
family wiring — one forward/train step on CPU, asserting output shapes and
no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import all_configs
from repro.launch.train import reduced_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.models.sharding import MeshAxes

ARCHS = sorted(all_configs())
B, S = 2, 64


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _batch(cfg, rng):
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    if cfg.encoder_layers:
        batch["frames"] = jnp.asarray(
            rng.normal(0, 1, (B, S, cfg.d_model)), jnp.float32
        )
        batch["tokens"] = batch["labels"] = toks[:, : S // 8]
    if cfg.frontend == "patch_stub":
        M.VLM_PATCH_TOKENS = 8
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(0, 1, (B, 8, cfg.d_model)), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_forward_and_train_step(arch, mesh):
    cfg = reduced_config(all_configs()[arch])
    rng = np.random.default_rng(hash(arch) % 2**31)
    batch = _batch(cfg, rng)
    params = M.init_params(cfg, jax.random.key(0), jnp.float32)
    axes = MeshAxes()
    with jax.set_mesh(mesh):
        lg, _ = M.forward(params, cfg, batch, axes, mode="train")
        loss, grads = jax.value_and_grad(M.loss_fn)(params, cfg, batch, axes)
    seq = batch["tokens"].shape[1] + (
        8 if cfg.frontend == "patch_stub" else 0
    )
    assert lg.shape == (B, seq, cfg.padded_vocab)
    assert not bool(jnp.isnan(lg).any()), "NaN in logits"
    assert not bool(jnp.isnan(loss)), "NaN loss"
    assert 1.0 < float(loss) < 20.0, f"loss scale off: {float(loss)}"
    gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0.0, "degenerate gradients"


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_one_sgd_step_changes_params(arch, mesh):
    cfg = reduced_config(all_configs()[arch])
    rng = np.random.default_rng(0)
    batch = _batch(cfg, rng)
    params = M.init_params(cfg, jax.random.key(1), jnp.float32)
    axes = MeshAxes()
    with jax.set_mesh(mesh):
        grads = jax.grad(M.loss_fn)(params, cfg, batch, axes)
        new = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
        delta = sum(
            float(jnp.sum(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(params))
        )
    assert delta > 0.0

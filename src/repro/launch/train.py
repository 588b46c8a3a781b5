"""End-to-end training driver.

Runs real steps on the local device(s): data pipeline -> jitted microbatched
train step -> periodic async checkpoints, with crash-safe restart (resumes
from the latest complete snapshot, including pipeline state).  The same
code path the dry-run lowers is executed here for real.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
      --steps 50 --batch 8 --seq 256 --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import all_configs, get_config
from ..data.pipeline import PipelineState, TokenPipeline
from ..models import model as M
from ..models.sharding import axes_for_mesh
from ..train import optimizer as opt_mod
from ..train.checkpoint import CheckpointManager
from ..train.trainer import make_train_step
from .mesh import make_host_mesh


def reduced_config(cfg, *, layers=2, d_model=128, vocab=512):
    """Shrink an arch config to a CPU-trainable size, same family wiring."""
    sb = cfg.superblock
    n_layers = max(layers * sb, sb) + cfg.remainder_layers
    kw = dict(
        n_layers=n_layers,
        d_model=d_model,
        n_heads=4,
        n_kv_heads=max(1, 4 * cfg.n_kv_heads // cfg.n_heads),
        d_ff=d_model * 3,
        vocab=vocab,
        head_dim=d_model // 4,
        dtype="float32",
        chunk_q=64,
        la_chunk=16,
    )
    if cfg.n_experts:
        kw.update(n_experts=4, moe_top_k=min(cfg.moe_top_k, 2),
                  moe_dff=d_model * 3)
    if cfg.encoder_layers:
        kw.update(encoder_layers=2)
    if cfg.family == "rwkv":
        kw.update(rwkv_head_dim=d_model // 4)
    if cfg.attn_every:
        kw.update(mamba_d_state=16, mamba_head_dim=d_model // 4)
    return dataclasses.replace(cfg, **kw)


def build_batch(pipe, cfg, shape_batch, seq):
    b = pipe.global_batch(shape_batch)
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    if cfg.encoder_layers:
        rng = np.random.default_rng(0)
        batch["frames"] = jnp.asarray(
            rng.normal(0, 1, (shape_batch, seq, cfg.d_model)), jnp.float32
        )
    if cfg.frontend == "patch_stub":
        rng = np.random.default_rng(0)
        M.VLM_PATCH_TOKENS = min(M.VLM_PATCH_TOKENS, seq // 4)
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(0, 1, (shape_batch, M.VLM_PATCH_TOKENS, cfg.d_model)),
            jnp.float32,
        )
    return batch


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    choices=sorted(all_configs()))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="shrink the config for CPU execution")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    mesh = make_host_mesh()
    axes = axes_for_mesh(mesh)

    opt_name = "adamw"
    optimizer = opt_mod.get_optimizer(opt_name, lr=args.lr)
    step_fn = jax.jit(make_train_step(cfg, axes, optimizer, args.micro))

    pipe = TokenPipeline(cfg.vocab, args.seq, n_shards=1, seed=0)
    mgr = CheckpointManager(args.ckpt_dir)
    start = 0
    restored, extra = mgr.restore()
    with jax.set_mesh(mesh):
        if restored is not None:
            print(f"restored step {extra['step']}")
            params = restored["params"]
            opt_state = restored["opt"]
            start = extra["step"]
            pipe.state = PipelineState.from_dict(extra["pipeline"])
        else:
            params = M.init_params(cfg, jax.random.key(0))
            opt_state = optimizer.init(params)

        losses = []
        for step in range(start, args.steps):
            batch = build_batch(pipe, cfg, args.batch, args.seq)
            t0 = time.time()
            params, opt_state, metrics = step_fn(
                params, opt_state, batch, jnp.asarray(step, jnp.int32)
            )
            loss = float(metrics["loss"])
            losses.append(loss)
            print(
                f"step {step:4d} loss {loss:8.4f} "
                f"gnorm {float(metrics['grad_norm']):8.3f} "
                f"{time.time()-t0:6.2f}s",
                flush=True,
            )
            if (step + 1) % args.ckpt_every == 0:
                mgr.save_async(
                    step + 1,
                    {"params": params, "opt": opt_state},
                    extra={"step": step + 1,
                           "pipeline": pipe.state.as_dict()},
                )
        mgr.wait()
    if len(losses) > 2:
        print(f"loss: first {losses[0]:.4f} -> last {losses[-1]:.4f} "
              f"({'DECREASED' if losses[-1] < losses[0] else 'no decrease'})")
    return losses


if __name__ == "__main__":
    main()

"""Serving driver: batched greedy generation over any selectable arch.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --batch 4 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs.base import all_configs, get_config
from ..models import model as M
from ..serve.engine import LMServer
from .mesh import make_host_mesh
from .train import reduced_config


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    choices=sorted(all_configs()))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    if cfg.encoder_layers or cfg.frontend != "none":
        raise SystemExit(
            "serve driver targets decoder-only archs; use examples/ for "
            "enc-dec and vlm flows"
        )
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        params = M.init_params(cfg, jax.random.key(0))
        server = LMServer(cfg, params)
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            0, cfg.vocab, (args.batch, args.prompt_len)
        ).astype(np.int32)
        t0 = time.time()
        out = server.generate(prompts, max_new=args.max_new)
        dt = time.time() - t0
    toks = args.batch * args.max_new
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s on this host)")
    print("first sequence:", out[0].tolist())
    return out


if __name__ == "__main__":
    main()

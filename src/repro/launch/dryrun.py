import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh).

For each cell this proves the distribution config is coherent without
hardware: the jitted step (train / prefill / decode per the shape's kind)
is lowered with ShapeDtypeStruct stand-ins (no allocation), compiled for
the production mesh, and its ``memory_analysis`` / ``cost_analysis`` /
collective schedule are recorded for EXPERIMENTS.md §Dry-run and the
roofline analysis (§Roofline).

Usage:
  python -m repro.launch.dryrun --arch internlm2-20b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  python -m repro.launch.dryrun --all --probes   # + roofline probe modules
"""
import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from ..configs.base import SHAPES, cells, get_config
from ..models import model as M
from ..models.sharding import axes_for_mesh
from ..train import optimizer as opt_mod
from ..train.trainer import make_train_step, pick_microbatches
from .mesh import make_production_mesh

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments"


def _mesh_tag(mesh) -> str:
    return "x".join(str(s) for s in mesh.devices.shape)


def lower_cell(cfg, shape, mesh, *, probe_blocks: int | None = None,
               extra_cfg: dict | None = None, force_micro: int | None = None):
    """Lower + compile one cell.  Returns (lowered, compiled, meta).

    probe_blocks: if set, builds a depth-reduced UNROLLED variant (the
    roofline probe) with that many superblocks and no remainder layers.
    """
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    if probe_blocks is not None:
        cfg = dataclasses.replace(
            cfg,
            n_layers=probe_blocks * cfg.superblock,
            encoder_layers=min(cfg.encoder_layers, probe_blocks)
            if cfg.encoder_layers else 0,
        )
    axes = axes_for_mesh(mesh)
    params = M.abstract_params(cfg, mesh)
    inputs = M.input_specs(cfg, shape, mesh)
    n_dp = 1
    for a in axes.dp:
        n_dp *= mesh.shape[a]

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_name = opt_mod.pick_for(cfg)
            optimizer = opt_mod.get_optimizer(opt_name)
            opt_state = jax.eval_shape(optimizer.init, params)
            opt_specs = optimizer.state_specs(M.param_pspecs(cfg, axes))
            opt_state = jax.tree.map(
                lambda s, sp: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=NamedSharding(mesh, sp)
                ),
                opt_state,
                opt_specs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
            )
            n_micro = force_micro or pick_microbatches(cfg, shape, n_dp)
            import jax.numpy as _jnp
            accum = _jnp.bfloat16 if opt_name == "adafactor" else _jnp.float32
            step_fn = make_train_step(cfg, axes, optimizer, n_micro,
                                      accum_dtype=accum)
            step = jax.ShapeDtypeStruct((), jnp.int32)
            # donate params+opt so the update aliases its inputs in place
            lowered = jax.jit(step_fn, donate_argnums=(0, 1)).lower(
                params, opt_state, inputs, step
            )
            meta = {"kind": "train", "optimizer": opt_name,
                    "n_micro": n_micro,
                    "accum_dtype": str(accum.__name__)}
        elif shape.kind == "prefill":
            def prefill_fn(p, b):
                return M.prefill(p, cfg, b, axes)

            lowered = jax.jit(prefill_fn).lower(params, inputs)
            meta = {"kind": "prefill"}
        else:  # decode
            def decode_fn(p, token, cache, pos):
                return M.decode_step(p, cfg, token, cache, pos, axes)

            lowered = jax.jit(decode_fn).lower(
                params, inputs["token"], inputs["cache"], inputs["pos"]
            )
            meta = {"kind": "decode"}
        compiled = lowered.compile()
    return lowered, compiled, meta


def run_cell(cfg, shape, mesh, *, probes: bool = False,
             save: bool = True, extra_cfg: dict | None = None,
             tag: str = "", force_micro: int | None = None) -> dict:
    from .. import roofline

    t0 = time.time()
    lowered, compiled, meta = lower_cell(cfg, shape, mesh,
                                         extra_cfg=extra_cfg,
                                         force_micro=force_micro)
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    rec = {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": _mesh_tag(mesh),
        **meta,
        "compile_s": round(time.time() - t0, 2),
        "memory": {
            "argument_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "alias_bytes_per_device": ma.alias_size_in_bytes,
        },
        "cost_analysis_raw": {
            "flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0),
        },
    }
    # collective schedule from the compiled HLO (while-body multipliers
    # resolved by the parser)
    txt = compiled.as_text()
    rec["collectives"] = roofline.parse_collectives(txt)
    rec["hlo_ops"] = roofline.op_census(txt)

    if probes:
        probe = {}
        for nb in (1, 2):
            _, c, _ = lower_cell(cfg, shape, mesh, probe_blocks=nb,
                                 extra_cfg=extra_cfg)
            pca = c.cost_analysis()
            pc = roofline.parse_collectives(c.as_text())
            probe[f"blocks{nb}"] = {
                "flops": pca.get("flops", 0.0),
                "bytes_accessed": pca.get("bytes accessed", 0.0),
                "collective_bytes": pc["total_bytes"],
            }
        rec["probe"] = probe

    if save:
        outdir = RESULTS_DIR / "dryrun"
        outdir.mkdir(parents=True, exist_ok=True)
        name = f"{cfg.name}_{shape.name}_{rec['mesh']}{tag}.json"
        (outdir / name).write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--probes", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    args = ap.parse_args()

    meshes = []
    if not args.multi_pod_only:
        meshes.append(make_production_mesh(multi_pod=False))
    if not args.single_pod_only:
        meshes.append(make_production_mesh(multi_pod=True))

    todo = []
    if args.all:
        todo = [(c, s) for c, s, skip in cells() if not skip]
    else:
        todo = [(get_config(args.arch), SHAPES[args.shape])]

    failures = []
    for cfg, shape in todo:
        for mesh in meshes:
            label = f"{cfg.name} x {shape.name} @ {_mesh_tag(mesh)}"
            try:
                probes = args.probes and len(mesh.shape) == 2
                rec = run_cell(cfg, shape, mesh, probes=probes)
                print(
                    f"OK   {label}: compile {rec['compile_s']}s, "
                    f"temp/dev {rec['memory']['temp_bytes_per_device']/2**30:.2f} GiB, "
                    f"args/dev {rec['memory']['argument_bytes_per_device']/2**30:.2f} GiB, "
                    f"coll {rec['collectives']['total_bytes']/2**30:.2f} GiB",
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001
                failures.append((label, repr(e)))
                print(f"FAIL {label}: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        for l, e in failures:
            print(" ", l, e)
        raise SystemExit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    main()

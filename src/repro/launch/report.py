"""Generate the §Dry-run and §Roofline tables from experiments/dryrun/*.json.

  PYTHONPATH=src python -m repro.launch.report > experiments/roofline.md
"""
from __future__ import annotations

import json
import pathlib

from .. import roofline
from ..configs.base import SHAPES, get_config

DRYRUN = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"
CHIPS = {"16x16": 256, "2x16x16": 512}
# the production meshes the dry run lowers for are v5e pods
TARGET_KIND = "TPU v5 lite"


def load_cells() -> list[dict]:
    return [json.loads(f.read_text()) for f in sorted(DRYRUN.glob("*.json"))]


def roofline_row(rec: dict) -> dict:
    cfg = get_config(rec["arch"])
    shape = SHAPES[rec["shape"]]
    chips = CHIPS[rec["mesh"]]
    n_micro = rec.get("n_micro", 1)
    ana = roofline.analytic_flops(cfg, shape, n_micro)
    flops = ana["total_flops"]
    if "probe" in rec:
        # grounded per-block HLO numbers, extrapolated to full depth
        ext = roofline.probe_extrapolate(rec["probe"], cfg.n_blocks)
        hbm = ext["bytes_accessed"] * chips  # probes report per-device
        probe_flops = ext["flops"] * chips
    else:
        hbm = rec["cost_analysis_raw"]["bytes_accessed"] * chips
        probe_flops = rec["cost_analysis_raw"]["flops"] * chips
    if hbm <= 0:
        # cross-depth fusion differences can make the probe delta
        # degenerate; fall back to the analytic traffic model
        hbm = roofline.analytic_hbm_bytes(cfg, shape, n_micro)
    coll = rec["collectives"]["total_bytes"]
    terms = roofline.roofline_terms(flops, hbm, coll, chips, TARGET_KIND)
    model_flops = ana["model_flops_6nd"]
    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": rec["mesh"],
        "kind": rec["kind"],
        "compute_s": terms["compute_s"],
        "memory_s": terms["memory_s"],
        "collective_s": terms["collective_s"],
        "dominant": terms["dominant"].replace("_s", ""),
        "roofline_fraction": terms["roofline_fraction"],
        "flops_analytic": flops,
        "flops_probe": probe_flops,
        "model_flops_6nd": model_flops,
        "useful_ratio": model_flops / flops if flops else 0.0,
        "hbm_bytes": hbm,
        "coll_bytes": coll,
        "temp_gib_dev": rec["memory"]["temp_bytes_per_device"] / 2**30,
        "args_gib_dev": rec["memory"]["argument_bytes_per_device"] / 2**30,
    }


def main():
    cells = load_cells()
    rows = [roofline_row(r) for r in cells if r["mesh"] == "16x16"]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    print("| arch | shape | compute_s | memory_s | collective_s | dominant "
          "| roofline_frac | 6ND/HLO | temp GiB/dev |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.2e} "
            f"| {r['memory_s']:.2e} | {r['collective_s']:.2e} "
            f"| {r['dominant']} | {r['roofline_fraction']:.2f} "
            f"| {r['useful_ratio']:.2f} | {r['temp_gib_dev']:.1f} |"
        )
    print()
    # multi-pod pass summary
    mp = [r for r in cells if r["mesh"] == "2x16x16"]
    print(f"multi-pod (2x16x16) cells passed: {len(mp)}")


if __name__ == "__main__":
    main()

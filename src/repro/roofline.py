"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds, against the published
peaks of the chip named by its ``device_kind`` (:data:`CHIP_PEAKS`):

  compute_s    = FLOPs / (chips * peak bf16 FLOP/s)
  memory_s     = HBM bytes / (chips * HBM bytes/s)
  collective_s = collective bytes / (chips * ICI bytes/s per link)

Sources:
  * ``parse_collectives`` extracts every all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute from the compiled HLO
    text, *including ops inside scan while-bodies*: the parser builds the
    computation call graph, finds each while loop's trip count from its
    condition's comparison constant, and multiplies nested ops accordingly.
    XLA's ``cost_analysis`` counts while bodies once, so this multiplier
    recovery is what makes scanned-layer models analyzable at all.
  * FLOPs / HBM bytes come from depth-probe extrapolation
    (``probe_extrapolate``): the compiled cost_analysis of unrolled 1- and
    2-superblock variants gives exact per-block costs including fusion
    effects; totals are base + per_block * n_blocks.  An analytic model
    (``analytic_flops``) cross-checks the probes; tests assert both agree on
    fully-unrolled small configs.
"""
from __future__ import annotations

import re
from collections import defaultdict

# --- published chip peaks, keyed by jax ``Device.device_kind`` -----------
# TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB HBM at 819 GB/s, and
# 1,600 Gbit/s of inter-chip interconnect over four links (50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes": 16 * 1024 ** 3,
        "hbm_bw": 819e9,
        "ici_bw_per_link": 50e9,
    },
}
MXU_MIN_DIM = 128


def chip_peaks(device_kind: str) -> dict:
    """Published peaks of the chip ``device_kind`` names (as
    ``jax.devices()[0].device_kind`` reports it).  A chip that is not in
    :data:`CHIP_PEAKS` is an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(CHIP_PEAKS)}"
        ) from None

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}


def _shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string, e.g. 'bf16[2,1024,512]{2,1,0}' or a
    tuple '(f32[8], f32[8])'."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# computation headers have nested parens in tuple-typed params, e.g.
#   %wide.region_0.1_spmd.clone (arg: (s32[], f32[8,16]{1,0})) -> (...) {
_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+) \(.*-> .*\{\s*$")
_OP_RE = re.compile(
    r"^\s+(?:ROOT )?%?([\w\.\-]+) = ((?:\([^=]*?\)|[\w\[\]{},\. ]+?)) "
    r"([\w\-]+)\((.*)$"
)


def _split_computations(txt: str) -> dict[str, list[str]]:
    comps: dict[str, list[str]] = {}
    cur = None
    for line in txt.splitlines():
        m = _COMP_RE.match(line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            continue
        if cur is not None:
            if line.startswith("}"):
                cur = None
            else:
                comps[cur].append(line)
    return comps


def parse_collectives(txt: str) -> dict:
    """Collective bytes from compiled HLO text with while-loop multipliers.

    Returns {'by_kind': {kind: bytes}, 'counts': {kind: n}, 'total_bytes'}.
    """
    comps = _split_computations(txt)

    # per-computation: collective (kind, bytes), calls (callee, trip_mult)
    coll: dict[str, list] = defaultdict(list)
    calls: dict[str, list] = defaultdict(list)
    trip_of_cond: dict[str, int] = {}

    for cname, lines in comps.items():
        for line in lines:
            m = _OP_RE.match(line)
            if m is None:
                continue
            _, rtype, op, rest = m.groups()
            if op in COLLECTIVES or op in {c + "-start" for c in COLLECTIVES}:
                kind = op.replace("-start", "")
                coll[cname].append((kind, _shape_bytes(rtype)))
            if op == "while":
                mb = re.search(r"body=%?([\w\.\-]+)", rest)
                mc = re.search(r"condition=%?([\w\.\-]+)", rest)
                if mb:
                    trip = _trip_count(comps.get(mc.group(1), [])) if mc else 1
                    calls[cname].append((mb.group(1), trip))
                    if mc:
                        trip_of_cond[mb.group(1)] = trip
            else:
                for mm in re.finditer(
                    r"(?:calls|to_apply|body|condition|branch_computations)="
                    r"[{%]?([\w\.\-, %]+)", rest
                ):
                    for callee in re.split(r"[,\s]+", mm.group(1)):
                        callee = callee.strip().lstrip("%")
                        if callee and callee in comps:
                            calls[cname].append((callee, 1))

    # propagate multipliers from ENTRY through the call graph
    m = re.search(r"^ENTRY %?([\w\.\-]+)", txt, re.M)
    entry = m.group(1) if m else next(iter(comps), None)

    by_kind: dict[str, float] = defaultdict(float)
    counts: dict[str, int] = defaultdict(int)
    seen_stack = set()

    def walk(cname: str, mult: float):
        if cname in seen_stack:  # cycle guard
            return
        seen_stack.add(cname)
        for kind, b in coll.get(cname, ()):
            by_kind[kind] += b * mult
            counts[kind] += 1
        for callee, trip in calls.get(cname, ()):
            walk(callee, mult * trip)
        seen_stack.discard(cname)

    if entry:
        walk(entry, 1.0)
    return {
        "by_kind": dict(by_kind),
        "counts": dict(counts),
        "total_bytes": float(sum(by_kind.values())),
    }


def _trip_count(cond_lines: list[str]) -> int:
    """Trip count from a while condition: the comparison constant."""
    best = 1
    for line in cond_lines:
        m = re.search(r"constant\((\d+)\)", line)
        if m:
            best = max(best, int(m.group(1)))
    return best


def op_census(txt: str) -> dict:
    """Counts of interesting ops in the entry module (reshape/transpose
    pressure, fusion counts — the 'profile' for the perf loop)."""
    census: dict[str, int] = defaultdict(int)
    for op in ("fusion", "reshape", "transpose", "copy", "while",
               "dynamic-slice", "dynamic-update-slice", "scatter", "gather",
               *COLLECTIVES):
        census[op] = len(re.findall(rf"= [\w\[\]{{}},\. ]+ {op}\(", txt))
    return dict(census)


# --------------------------------------------------------------------------
# probe extrapolation + analytic model
# --------------------------------------------------------------------------
def probe_extrapolate(probe: dict, n_blocks: int) -> dict:
    """Per-block costs from unrolled 1-/2-block probes -> full-depth totals.

    total(n) = base + per_block * n, from total(1) and total(2)."""
    one, two = probe["blocks1"], probe["blocks2"]
    out = {}
    for key in ("flops", "bytes_accessed", "collective_bytes"):
        per = two[key] - one[key]
        base = one[key] - per
        out[key] = base + per * n_blocks
        out[f"{key}_per_block"] = per
    return out


def analytic_flops(cfg, shape, n_micro: int = 1) -> dict:
    """Closed-form FLOPs for one step of the cell (global, all chips).

    Forward matmul flops 2*N_active_nonembed*T + attention; train multiplies
    by 4 (bwd 2x + full-remat recompute 1x); microbatching does not change
    totals.  Cross-checked against XLA cost_analysis in tests."""
    B, S = shape.global_batch, shape.seq_len
    kind = shape.kind
    d, hd = cfg.d_model, cfg.hd
    kinds = cfg.layer_kinds()
    ffns = cfg.ffn_kinds()
    sb = cfg.superblock

    def layer_flops(i: int, T: int, S_ctx: int) -> float:
        k, f = kinds[i % sb], ffns[i % sb]
        fl = 0.0
        if k in ("attn", "local", "global"):
            proj = 2 * T * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd \
                + 2 * T * cfg.n_heads * hd * d
            # EXECUTED flops: the q-chunked einsum computes every (q, k)
            # score and masks afterwards, so causal masking does NOT halve
            # the work, and naive local attention pays the full context;
            # the sliced-KV path (local_slice_opt) pays window + chunk.
            if k == "local" and cfg.local_window and kind != "decode":
                if cfg.local_slice_opt:
                    cq = min(cfg.chunk_q, T // B)
                    ctx = min(cfg.local_window + cq, S_ctx)
                else:
                    ctx = S_ctx
            elif k == "local" and cfg.local_window and kind == "decode":
                ctx = min(cfg.local_window, S_ctx)
            else:
                ctx = S_ctx
            att = 2 * 2 * B * cfg.n_heads * (T // B) * ctx * hd
            fl += proj + att
        elif k == "mamba":
            di = cfg.mamba_expand * d
            N = cfg.mamba_d_state
            fl += 2 * T * d * (2 * di + 2 * (di // cfg.mamba_head_dim) * N
                               + di // cfg.mamba_head_dim) \
                + 2 * T * di * d
            H = di // cfg.mamba_head_dim
            c = cfg.la_chunk
            fl += 2 * T * H * (2 * c * N + 2 * N * cfg.mamba_head_dim
                               + c * cfg.mamba_head_dim)
        elif k == "rwkv":
            fl += 2 * T * d * d * 5  # r,k,v,g,o
            H = d // cfg.rwkv_head_dim
            c = cfg.la_chunk
            dk = cfg.rwkv_head_dim
            fl += 2 * T * H * (2 * c * dk + 2 * dk * dk + c * dk)
        if f == "dense":
            fl += 2 * 3 * T * d * cfg.d_ff
        elif f == "moe":
            fl += 2 * T * d * cfg.n_experts  # router
            fl += 2 * 3 * T * cfg.moe_top_k * cfg.capacity_factor * d * \
                (cfg.moe_dff or cfg.d_ff)
            if cfg.dense_residual:
                fl += 2 * 3 * T * d * cfg.d_ff
        elif f == "rwkv_cm":
            fl += 2 * T * (2 * d * cfg.d_ff + d * d)
        return fl

    if kind == "decode":
        T = B  # one token per sequence
        S_ctx = S
    else:
        T = B * S
        S_ctx = S

    fwd = 0.0
    n_full = cfg.n_layers
    for i in range(n_full):
        fwd += layer_flops(i, T, S_ctx)
    if cfg.encoder_layers and kind != "decode":
        # encoder over frames + decoder cross-attention
        Te = B * S
        for i in range(cfg.encoder_layers):
            fwd += (2 * Te * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd
                    + 2 * Te * cfg.n_heads * hd * d
                    + 2 * 2 * B * cfg.n_heads * S * S * hd * 0.5
                    + 2 * 3 * Te * d * cfg.d_ff)
        Td = T // 8 if kind != "decode" else T
        fwd += n_full * (2 * Td * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd
                         + 2 * Td * cfg.n_heads * hd * d
                         + 2 * 2 * B * cfg.n_heads * (Td // B) * S * hd)
    # logits / loss head
    T_head = (B * (S // 8) if cfg.encoder_layers else T) if kind == "train" \
        else B
    fwd += 2 * T_head * d * cfg.vocab

    mult = 4.0 if kind == "train" else 1.0  # bwd 2x + remat recompute 1x
    # useful model flops: 6*N_active*D for training, 2*N_active*D forward
    per_tok = 6 if kind == "train" else 2
    model_flops = per_tok * cfg.params_count()[1] * (
        T_head if kind == "train" else T
    )
    return {
        "fwd_flops": fwd,
        "total_flops": fwd * mult,
        "model_flops_6nd": model_flops,
    }


def analytic_hbm_bytes(cfg, shape, n_micro: int = 1) -> float:
    """Estimated HBM traffic per step (global, all chips) — the fallback
    when probe extrapolation is degenerate (negative per-block deltas from
    cross-depth fusion differences).

    train:   params read 3x (fwd + remat-fwd + bwd) + grad write/read (4B)
             + optimizer state r/w + activation traffic
    prefill: params 1x + KV cache write + activations
    decode:  params 1x + full cache read + tiny activations
    """
    total, active = cfg.params_count()
    B, S = shape.global_batch, shape.seq_len
    pbytes = total * 2  # bf16
    act_unit = cfg.d_model * 2
    if shape.kind == "train":
        tokens = B * S
        act = 8 * tokens * act_unit * cfg.n_layers
        grads = total * 4 * 2
        opt = total * 2 * 2
        return 3 * pbytes + grads + opt + act
    if shape.kind == "prefill":
        tokens = B * S
        cache = (2 * cfg.n_layers * B * S * cfg.n_kv_heads * cfg.hd * 2
                 if cfg.n_heads else 0)
        act = 6 * tokens * act_unit * cfg.n_layers
        return pbytes + cache + act
    # decode
    cache = 2 * cfg.n_layers * B * S * cfg.n_kv_heads * cfg.hd * 2
    return pbytes + cache


# --------------------------------------------------------------------------
# query-kernel rooflines: achieved vs peak bandwidth per fused kernel
# --------------------------------------------------------------------------
# Minimum-traffic models for the PR-7 tiled kernel family: each counts the
# bytes a perfect cache would still have to move (every input once, every
# output once).  Achieved GB/s from a wall-clock measurement over these
# bytes is therefore a *lower bound* on true traffic — re-streamed tiles
# only push the real number higher, so peak_fraction is conservative.

def bytes_box_hits_tiled(n: int, nq: int, d: int,
                         box_bytes: int = 4) -> int:
    """(n boxes x nq windows) intersection-mask kernel traffic.

    ``box_bytes=2`` models the compressed bf16-MBB layout — the knob whose
    bandwidth halving this roofline exists to show."""
    return 2 * n * d * box_bytes + 2 * nq * d * 4 + n * nq * 4


def bytes_pair_window_ids(p: int, s: int, d: int) -> int:
    """Fused (query, leaf) pair window scan: per pair one leaf block of
    points + ids + count + one query box in, one id row + count out."""
    per_pair = s * d * 4 + s * 4 + 4 + 2 * d * 4 + s * 4 + 4
    return p * per_pair


def bytes_leaf_mindist_tiled(nq: int, n_l: int, d: int,
                             box_bytes: int = 4) -> int:
    """(nq x L) squared-mindist kernel traffic."""
    return 2 * n_l * d * box_bytes + nq * d * 4 + nq * n_l * 4


def bytes_pair_dist2(p: int, s: int, d: int) -> int:
    """Fused (query, leaf) candidate-distance kernel traffic."""
    per_pair = s * d * 4 + 4 + d * 4 + s * 4
    return p * per_pair


def kernel_roofline(bytes_moved: float, seconds: float,
                    device_kind: str) -> dict:
    """Achieved-vs-peak HBM bandwidth for one kernel invocation timed on
    the chip ``device_kind`` names (a CPU or interpret-mode timing has no
    roof here: it raises)."""
    if seconds <= 0:
        raise ValueError("seconds must be positive")
    bw = chip_peaks(device_kind)["hbm_bw"]
    achieved = bytes_moved / seconds
    return {
        "bytes": float(bytes_moved),
        "seconds": float(seconds),
        "achieved_gbps": achieved / 1e9,
        "peak_gbps": bw / 1e9,
        "peak_fraction": achieved / bw,
    }


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   chips: int, device_kind: str) -> dict:
    peaks = chip_peaks(device_kind)
    compute_s = flops / (chips * peaks["bf16_flops"])
    memory_s = hbm_bytes / (chips * peaks["hbm_bw"])
    collective_s = coll_bytes / (chips * peaks["ici_bw_per_link"])
    terms = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    bound = max(compute_s, memory_s, collective_s)
    terms["roofline_fraction"] = compute_s / bound if bound > 0 else 0.0
    return terms

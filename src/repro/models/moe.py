"""Mixture-of-Experts FFN: shard_map expert parallelism, sort-based dispatch.

Lesson recorded from the dry-run (EXPERIMENTS.md §Perf): a jit-level
sort/scatter dispatch leaves GSPMD unable to shard the data-dependent
gather/scatter — it replicates the (T*k, D) dispatch buffers and a 235B MoE
prefill explodes to 142 GiB/device of temp.  The fix is explicit SPMD:
``shard_map`` over (dp x tp), where each model-axis rank owns E/tp experts
and dispatches *its own* tokens locally:

  * routing (softmax + top-k) is computed per shard (replicated math across
    tp — negligible next to expert FLOPs);
  * tokens whose expert lives on another rank fall into a sentinel row, so
    every gather/scatter is shard-local with static shapes;
  * partial expert outputs are summed with ``psum`` over the model axis
    (the standard EP combine);
  * dispatch runs in token chunks (lax.scan) to bound live buffers.

Capacity semantics are the usual Switch drop: per chunk, each expert
accepts ``capacity_factor * chunk * k / E`` tokens; overflow falls back to
the residual stream.  Arctic's dense-residual FFN runs outside the
shard_map as a plain (TP-sharded) SwiGLU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import Param, swiglu

TOKEN_CHUNK = 8192


def moe_defs(cfg) -> dict:
    d, f, e = cfg.d_model, cfg.moe_dff or cfg.d_ff, cfg.n_experts
    return {
        "router": Param((d, e), (None, None)),
        "w1": Param((e, d, f), ("tp", "fsdp", None)),
        "w3": Param((e, d, f), ("tp", "fsdp", None)),
        "w2": Param((e, f, d), ("tp", None, "fsdp")),
    }


def capacity(cfg, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.moe_top_k / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def _dispatch_chunk(xc, ec, wc, w1, w3, w2, lo, E_l, C, dtype):
    """Shard-local dispatch of one token chunk.

    xc: (T, D); ec/wc: (T, K) expert ids / weights; experts [lo, lo+E_l)
    live here.  Returns (T, D) partial output (zeros for remote experts).
    """
    T, D = xc.shape
    K = ec.shape[1]
    flat_e = ec.reshape(-1)
    flat_w = wc.reshape(-1)
    tok = jnp.repeat(jnp.arange(T), K)
    local = (flat_e >= lo) & (flat_e < lo + E_l)
    fe = jnp.where(local, flat_e - lo, E_l)          # sentinel expert E_l
    order = jnp.argsort(fe)
    sfe, stok, sw = fe[order], tok[order], flat_w[order]
    first = jnp.searchsorted(sfe, jnp.arange(E_l + 1))
    pos = jnp.arange(T * K) - first[sfe]
    drop = (pos >= C) | (sfe == E_l)
    sslot = jnp.where(drop, C, pos)
    buf = jnp.zeros((E_l + 1, C + 1, D), dtype)
    buf = buf.at[sfe, sslot].set(xc[stok])
    buf = buf[:E_l, :C]

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w3)
    ob = jnp.einsum("ecf,efd->ecd", h, w2)           # (E_l, C, D)

    ge = jnp.minimum(sfe, E_l - 1)
    gs = jnp.minimum(sslot, C - 1)
    contrib = jnp.where(drop[:, None], 0.0, ob[ge, gs] * sw[:, None])
    return jnp.zeros((T, D), dtype).at[stok].add(contrib)


def moe_ffn(p, cfg, x, axes):
    """x: (B, S, D) -> (B, S, D)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    dp = axes.dp if len(axes.dp) > 1 else axes.dp[0]
    tp = axes.tp

    mesh = jax.sharding.get_abstract_mesh()
    n_dp = 1
    for a in (axes.dp if isinstance(axes.dp, tuple) else (axes.dp,)):
        n_dp *= mesh.shape[a]
    batch_spec = dp if B % n_dp == 0 else None

    def body(router, w1, w3, w2, xt):
        E_l = w1.shape[0]
        my = jax.lax.axis_index(tp) * E_l
        Bl, Sl, _ = xt.shape
        T = Bl * Sl
        xf = xt.reshape(T, D)
        gates = jax.nn.softmax(
            xf.astype(jnp.float32) @ router.astype(jnp.float32), axis=-1
        )
        topw, tope = jax.lax.top_k(gates, K)
        topw = (topw / jnp.sum(topw, -1, keepdims=True)).astype(xt.dtype)

        chunk = min(TOKEN_CHUNK, T)
        while T % chunk:
            chunk -= 1
        n_ch = T // chunk
        C = capacity(cfg, chunk)

        if n_ch == 1:
            out = _dispatch_chunk(
                xf, tope, topw, w1, w3, w2, my, E_l, C, xt.dtype
            )
        else:
            def step(_, ins):
                xc, ec, wc = ins
                return 0, _dispatch_chunk(
                    xc, ec, wc, w1, w3, w2, my, E_l, C, xt.dtype
                )

            _, outs = jax.lax.scan(
                step, 0,
                (
                    xf.reshape(n_ch, chunk, D),
                    tope.reshape(n_ch, chunk, K),
                    topw.reshape(n_ch, chunk, K),
                ),
            )
            out = outs.reshape(T, D)
        out = jax.lax.psum(out, tp)  # EP combine across expert shards
        return out.reshape(Bl, Sl, D)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, None),        # router: replicated
            P(tp, None, None),    # experts sharded over the model axis
            P(tp, None, None),
            P(tp, None, None),
            P(batch_spec, None, None),
        ),
        out_specs=P(batch_spec, None, None),
    )
    out = fn(p["router"], p["w1"], p["w3"], p["w2"], x)

    if cfg.dense_residual:
        out = out + swiglu(x, p["dense"]["w1"], p["dense"]["w3"],
                           p["dense"]["w2"])
    return out


def aux_loss(p, cfg, x):
    """Load-balancing auxiliary loss (Switch-style f*P)."""
    T = x.shape[0] * x.shape[1]
    gates = jax.nn.softmax(
        x.reshape(T, -1).astype(jnp.float32) @ p["router"].astype(jnp.float32),
        axis=-1,
    )
    _, tope = jax.lax.top_k(gates, cfg.moe_top_k)
    onehot = jax.nn.one_hot(tope, cfg.n_experts).sum(1)  # (T, E)
    f = onehot.mean(0)
    prob = gates.mean(0)
    return cfg.n_experts * jnp.sum(f * prob)

"""Sparse MoE layer: softmax-top-k router + capacity-factor one-hot dispatch.

The dispatch einsum is the GSPMD-friendly formulation (Switch/MaxText style):
tokens are grouped, each group gets ``C = ceil(S_g * k * cf / E)`` slots per
expert, and dispatch/combine are einsums against a (G, S*k, E, C) one-hot.
With the expert axis sharded on "model" and groups on "data", XLA emits the
expert-parallel all-to-all. Router math runs in f32.

``moe_apply`` also returns the routed expert ids per token — the activation
trace the paper's predictor is trained on (core/tracing.py consumes it).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.launch import shardctx
from repro.models.common import dense_init, ffn_apply, ffn_init

# tokens per dispatch group (see DESIGN.md §8 / EXPERIMENTS.md §Perf —
# smaller groups cut dispatch-einsum FLOPs linearly at fixed capacity slack)
DEFAULT_GROUP = 4096


def moe_init(key, cfg, dtype):
    m = cfg.moe
    d, e, f = cfg.d_model, m.num_experts, m.d_ff_expert
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "w_router": dense_init(k1, d, e, jnp.float32),
        "w_gate": dense_init(k2, d, e * f, dtype).reshape(e, d, f),
        "w_up": dense_init(k3, d, e * f, dtype).reshape(e, d, f),
        "w_down": dense_init(k4, f, e * d, dtype).reshape(e, f, d),
    }
    if m.num_shared:
        p["shared"] = ffn_init(k5, d, m.num_shared * f, dtype)
    return p


def route(p, cfg, x):
    """Router: softmax over experts then top-k, renormalised (DeepSeek-V2).

    Returns (weights (B,T,k) f32, idx (B,T,k) i32, probs (B,T,E) f32).
    """
    m = cfg.moe
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), p["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-9)
    return w, idx, probs


def aux_load_balance_loss(cfg, probs, idx):
    """Switch-style load-balance loss: E * sum_e density_e * usage_e."""
    e = cfg.moe.num_experts
    density = jnp.mean(probs.reshape(-1, e), axis=0)             # router mass
    usage = jnp.mean(jax.nn.one_hot(idx.reshape(-1), e), axis=0) * \
        (1.0 / cfg.moe.top_k)                                    # token share
    return e * jnp.sum(density * usage)


def capacity(cfg, group_tokens: int) -> int:
    m = cfg.moe
    return max(1, math.ceil(group_tokens * m.top_k * m.capacity_factor
                            / m.num_experts))


def moe_gather_apply(p, cfg, x, w, idx):
    """Batch-1-style decode path: gather ONLY the routed experts' weights
    instead of running every expert over a capacity buffer (§Perf B1 — the
    paper's expert-fetch model at the sharded level). Worth it whenever
    n*top_k < num_experts: weight traffic drops ~E/(n*k)x.

    x: (B,T,D); w: (B,T,k); idx: (B,T,k) -> (B,T,D)."""
    m = cfg.moe
    b, t, d = x.shape
    flat_idx = idx.reshape(-1)                                # (n*k,)
    wg = jnp.take(p["w_gate"], flat_idx, axis=0)              # (n*k, D, F)
    wu = jnp.take(p["w_up"], flat_idx, axis=0)
    wd = jnp.take(p["w_down"], flat_idx, axis=0)
    xf = jnp.repeat(x.reshape(b * t, d), m.top_k, axis=0)     # (n*k, D)
    g = jnp.einsum("nd,ndf->nf", xf, wg)
    u = jnp.einsum("nd,ndf->nf", xf, wu)
    y = jnp.einsum("nf,nfd->nd", jax.nn.silu(g) * u, wd)      # (n*k, D)
    y = (y.reshape(b, t, m.top_k, d)
         * w[..., None].astype(x.dtype)).sum(axis=2)
    if m.num_shared:
        y = y + ffn_apply(p["shared"], x, "swiglu")
    return y


def expert_group_ffn(wg, wu, wd, x):
    """ONE expert's SwiGLU FFN over a shipped token group — the unit of
    work a peer shard computes in the expert-parallel dispatch path
    (launch/sharding.expert_dispatch_ffn; serving engines model the same
    computation through their grouped slot-buffer program).

    wg/wu: (D, F); wd: (F, D); x: (N, D) token activations. Returns the
    (N, D) *unweighted* expert outputs — the router's top-k combine
    weights are applied by the caller after the outputs return, so the
    weighted sum happens exactly where the local path does it.
    Accumulates in f32 (matching the reference kernels), returns x.dtype.
    """
    xf = x.astype(jnp.float32)
    g = xf @ wg.astype(jnp.float32)
    u = xf @ wu.astype(jnp.float32)
    y = (jax.nn.silu(g) * u) @ wd.astype(jnp.float32)
    return y.astype(x.dtype)


def moe_apply(p, cfg, x, group_tokens: int = 0, decode: bool = False):
    """x: (B,T,D) -> (out, aux_loss, expert_idx (B,T,k))."""
    m = cfg.moe
    b, t, d = x.shape
    w, idx, probs = route(p, cfg, x)
    aux = aux_load_balance_loss(cfg, probs, idx)

    n = b * t
    # NOTE (§Perf B1, refuted for sharded serving): under expert-parallel
    # sharding the gather path makes GSPMD broadcast the selected experts'
    # weights to every device (+1.5 GB all-reduce per step on llama4
    # long_500k) — one-hot dispatch already computes on the owning shard.
    # The gather path pays off only on an UNSHARDED expert store (the edge
    # engine, serving/engine.py) — so it is opt-in via decode_gather.
    if decode and getattr(m, "decode_gather", False)             and n * m.top_k < m.num_experts:
        return moe_gather_apply(p, cfg, x, w, idx), aux, idx
    sg = min(group_tokens or m.dispatch_group or DEFAULT_GROUP, n)
    if n % sg:
        sg = n  # fall back to one group for awkward sizes (small tests)
    g = n // sg
    c = capacity(cfg, sg)

    xf = x.reshape(g, sg, d)
    idx_g = idx.reshape(g, sg, m.top_k)
    w_g = w.reshape(g, sg, m.top_k).astype(x.dtype)

    # expert one-hot per (token, k-slot), flattened to (G, S*k, E)
    onehot = jax.nn.one_hot(idx_g, m.num_experts, dtype=jnp.int32)
    oh_flat = onehot.reshape(g, sg * m.top_k, m.num_experts)
    # position of each slot within its expert's capacity buffer
    pos = jnp.cumsum(oh_flat, axis=1) - 1                        # (G,S*k,E)
    keep = (pos < c) & (oh_flat > 0)
    dispatch = (keep[..., None]
                & (pos[..., None] == jnp.arange(c)[None, None, None]))
    dispatch = dispatch.astype(x.dtype)                          # (G,S*k,E,C)

    # route tokens to expert buffers: each of the S*k slots maps to token s//k
    x_rep = jnp.repeat(xf, m.top_k, axis=1)                      # (G,S*k,D)
    x_e = jnp.einsum("gtec,gtd->gecd", dispatch, x_rep)          # (G,E,C,D)

    h_gate = jnp.einsum("gecd,edf->gecf", x_e, p["w_gate"])
    h_up = jnp.einsum("gecd,edf->gecf", x_e, p["w_up"])
    h = jax.nn.silu(h_gate) * h_up
    y_e = jnp.einsum("gecf,efd->gecd", h, p["w_down"])           # (G,E,C,D)

    # combine: per slot, gather its expert output, weight it, then sum the
    # k slots belonging to each token. NOTE (§Perf A5, refuted twice): both
    # folding w into the dispatch mask and reduce-scatter-constraining the
    # (g,t,d) output made GSPMD materialise a second (G,S*k,E,C) tensor /
    # reshard-churn — the 3-operand einsum below is what XLA shards best.
    w_rep = w_g.reshape(g, sg * m.top_k)
    y_slot = jnp.einsum("gtec,gecd,gt->gtd", dispatch, y_e, w_rep)
    y = y_slot.reshape(g, sg, m.top_k, d).sum(axis=2).reshape(b, t, d)

    if m.num_shared:
        y = y + ffn_apply(p["shared"], x, "swiglu")
    return y, aux, idx

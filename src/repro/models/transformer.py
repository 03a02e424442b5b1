"""Decoder assembly: blocks -> scan-over-layer-groups -> LM.

Layer layout (keeps HLO size ~O(pattern length), not O(num_layers)):
  head blocks   — ``moe.first_dense_layers`` unrolled layers (dense FFN)
  scan blocks   — ``G`` repetitions of ``block_pattern``; params/caches are
                  stacked with leading dim G and driven by ``jax.lax.scan``
  tail blocks   — ``num_layers`` remainder, unrolled

Every apply returns ``extras`` carrying routed-expert ids for MoE layers —
the raw material for the paper's activation traces.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.launch import shardctx
from repro.models import attention as attn
from repro.models import mla, moe, rglru, ssd
from repro.models.common import (dense_init, dtype_of, ffn_apply, ffn_init,
                                 rms_norm, rms_norm_init)

Params = Dict[str, Any]


def _layer_split(cfg):
    n_head = cfg.moe.first_dense_layers if cfg.moe else 0
    pat = len(cfg.block_pattern)
    rem = cfg.num_layers - n_head
    return n_head, rem // pat, rem % pat


def _layer_is_moe(cfg, layer_idx: int) -> bool:
    if cfg.moe is None:
        return False
    if cfg.layer_kinds()[layer_idx] == "ssd":
        return False
    return layer_idx >= cfg.moe.first_dense_layers


# ---------------------------------------------------------------------------
# Single block

def block_init(key, cfg, kind: str, is_moe: bool, dtype):
    ks = jax.random.split(key, 3)
    p: Params = {"ln1": rms_norm_init(cfg.d_model, dtype)}
    if kind == "mla":
        p["attn"] = mla.mla_init(ks[0], cfg, dtype)
    elif kind in ("global", "local", "chunked"):
        p["attn"] = attn.attn_init(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["rec"] = rglru.rglru_init(ks[0], cfg, dtype)
    elif kind == "ssd":
        p["ssd"] = ssd.ssd_init(ks[0], cfg, dtype)
        return p                                     # mamba block: no FFN
    p["ln2"] = rms_norm_init(cfg.d_model, dtype)
    if is_moe:
        p["moe"] = moe.moe_init(ks[1], cfg, dtype)
    else:
        dff = cfg.d_ff
        if cfg.moe is not None and cfg.moe.d_ff_dense:
            dff = cfg.moe.d_ff_dense
        p["ffn"] = ffn_init(ks[1], cfg.d_model, dff, dtype)
    return p


def block_cache_init(cfg, kind: str, batch: int, cache_len: int, dtype):
    if kind == "mla":
        return mla.mla_init_cache(cfg, batch, cache_len, dtype)
    if kind in ("global", "local", "chunked"):
        return attn.init_cache(cfg, kind, batch, cache_len, dtype)
    if kind == "rglru":
        return rglru.rglru_init_state(cfg, batch, dtype)
    if kind == "ssd":
        return ssd.ssd_init_state(cfg, batch, dtype)
    raise ValueError(kind)


# Attention kinds whose decode KV grows with the sequence — these page
# through block tables in the paged serving engine. Ring-buffer kinds
# (local/chunked) and recurrent kinds keep bounded per-request rows.
PAGED_KINDS = ("global", "mla")


def block_paged_cache_init(cfg, kind: str, num_blocks: int, block_size: int,
                           row_batch: int, dtype):
    """Per-layer decode cache for the block-paged serving engine.

    Paged kinds get a (num_blocks, block_size, ...) pool sharing one block-id
    space across layers (serving/kvpool.py); bounded kinds keep ``row_batch``
    contiguous rows exactly like ``block_cache_init`` (the scratch row
    included).
    """
    if kind == "mla":
        return mla.mla_paged_init_cache(cfg, num_blocks, block_size, dtype)
    if kind == "global":
        return attn.paged_init_cache(cfg, num_blocks, block_size, dtype)
    if kind in ("local", "chunked"):
        return attn.init_cache(cfg, kind, row_batch, 0, dtype)  # ring-sized
    # recurrent kinds have no DecodeCore decode path at all, so the paged
    # engine's paged_ok gate rejects them before reaching here
    raise ValueError(f"no paged decode cache for layer kind {kind!r}")


def block_paged_decode(p, cfg, kind: str, x, cache, tables, pos,
                       kernel=None):
    """Attention half of one paged decode step (ln1 + attend + residual).

    x: (N,1,D); tables: (N,W); pos: (N,). ``kernel`` selects the paged
    flash-decode backend (kernels/paged_attention.py) for every paged kind;
    None keeps each family's gather + dense-attend parity reference.
    Returns (x, new_cache).
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "mla":
        o, nc = mla.mla_paged_decode(p["attn"], cfg, h, cache, tables, pos,
                                     kernel=kernel)
    elif kind == "global":
        o, nc = attn.paged_attn_decode(p["attn"], cfg, h, cache, tables,
                                       pos, kernel=kernel)
    else:
        raise ValueError(f"layer kind {kind!r} does not page")
    return x + o, nc


def block_paged_prefill(p, cfg, kind: str, x, cache, table, t0, n_valid,
                        kernel=None):
    """Attention half of one paged prefill chunk for a single request.

    x: (1,C,D); table: (W,); t0/n_valid scalars. Same kernel selection as
    ``block_paged_decode`` — chunk tokens become kernel lanes, keeping the
    chunked-prefill stream token-identical to decode. Returns (x, new_cache).
    """
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "mla":
        o, nc = mla.mla_paged_prefill(p["attn"], cfg, h, cache, table, t0,
                                      n_valid, kernel=kernel)
    elif kind == "global":
        o, nc = attn.paged_attn_prefill(p["attn"], cfg, h, cache, table, t0,
                                        n_valid, kernel=kernel)
    else:
        raise ValueError(f"layer kind {kind!r} does not page")
    return x + o, nc


def block_paged_copy(cfg, kind: str, cache, src, dst):
    """Copy pool page ``src -> dst`` for one paged layer — the device side
    of copy-on-write when a request must write into a block it shares with
    siblings (prefix cache). Bounded (ring/recurrent) kinds have no pages
    and never share, so only paged kinds dispatch here."""
    if kind == "mla":
        return mla.mla_paged_copy_block(cache, src, dst)
    if kind == "global":
        return attn.paged_copy_block(cache, src, dst)
    raise ValueError(f"layer kind {kind!r} does not page")


def block_apply(p, cfg, kind: str, x, positions, mode: str,
                cache=None, pos=None, cache_len: int = 0):
    """Returns (x, new_cache, extras)."""
    extras: Params = {}
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "mla":
        out, new_cache = mla.mla_apply(p["attn"], cfg, h, positions, mode,
                                       cache, pos, cache_len)
    elif kind in ("global", "local", "chunked"):
        out, new_cache = attn.attn_apply(p["attn"], cfg, kind, h, positions,
                                         mode, cache, pos, cache_len)
    elif kind == "rglru":
        if mode == "decode":
            out, new_cache = rglru.rglru_step(p["rec"], cfg, h, cache)
        elif mode == "prefill":
            out, new_cache = rglru.rglru_apply_full(p["rec"], cfg, h,
                                                    return_state=True)
        else:
            out, new_cache = rglru.rglru_apply_full(p["rec"], cfg, h), None
    elif kind == "ssd":
        if mode == "decode":
            out, new_cache = ssd.ssd_step(p["ssd"], cfg, h, cache)
        elif mode == "prefill":
            out, new_cache = ssd.ssd_apply_full(p["ssd"], cfg, h,
                                                return_state=True)
        else:
            out, new_cache = ssd.ssd_apply_full(p["ssd"], cfg, h), None
        return x + out, new_cache, extras            # no FFN sub-block
    else:
        raise ValueError(kind)
    x = x + out

    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y, aux, idx = moe.moe_apply(p["moe"], cfg, h,
                                    decode=(mode == "decode"))
        extras["moe_aux"] = aux
        extras["experts"] = idx
    else:
        y = ffn_apply(p["ffn"], h, cfg.ffn_kind)
    return x + y, new_cache, extras


# ---------------------------------------------------------------------------
# Full decoder stack

def stack_init(key, cfg, dtype) -> Params:
    kinds = cfg.layer_kinds()
    n_head, n_groups, n_tail = _layer_split(cfg)
    pat = len(cfg.block_pattern)
    keys = jax.random.split(key, cfg.num_layers)

    head = [block_init(keys[i], cfg, kinds[i], _layer_is_moe(cfg, i), dtype)
            for i in range(n_head)]

    scan_params = []
    for j in range(pat):
        per_group = []
        for g in range(n_groups):
            li = n_head + g * pat + j
            per_group.append(block_init(keys[li], cfg, kinds[li],
                                        _layer_is_moe(cfg, li), dtype))
        scan_params.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
                           if n_groups else {})

    tail_base = n_head + n_groups * pat
    tail = [block_init(keys[tail_base + i], cfg, kinds[tail_base + i],
                       _layer_is_moe(cfg, tail_base + i), dtype)
            for i in range(n_tail)]
    return {"head": head, "scan": tuple(scan_params), "tail": tail}


def stack_cache_init(cfg, batch: int, cache_len: int, dtype) -> Params:
    kinds = cfg.layer_kinds()
    n_head, n_groups, n_tail = _layer_split(cfg)
    pat = len(cfg.block_pattern)

    def mk(i):
        return block_cache_init(cfg, kinds[i], batch, cache_len, dtype)

    head = [mk(i) for i in range(n_head)]
    scan = []
    for j in range(pat):
        per = [mk(n_head + g * pat + j) for g in range(n_groups)]
        scan.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per)
                    if n_groups else {})
    tail_base = n_head + n_groups * pat
    tail = [mk(tail_base + i) for i in range(n_tail)]
    return {"head": head, "scan": tuple(scan), "tail": tail}


def stack_apply(params, cfg, x, positions, mode: str,
                caches: Optional[Params] = None, pos=None, cache_len: int = 0):
    """Run all layers. Returns (x, new_caches, extras_list).

    extras_list: per-layer dicts for head/tail; for scanned groups the
    entries are stacked with leading dim G (one entry per pattern position).
    """
    kinds = cfg.layer_kinds()
    n_head, n_groups, n_tail = _layer_split(cfg)
    pat = len(cfg.block_pattern)
    use_cache = mode == "decode"        # prefill BUILDS caches, reads none
    new_caches: Params = {"head": [], "scan": None, "tail": []}
    extras_out = {"head": [], "scan": None, "tail": []}

    for i in range(n_head):
        c = caches["head"][i] if use_cache else None
        x, nc, ex = block_apply(params["head"][i], cfg, kinds[i], x,
                                positions, mode, c, pos, cache_len)
        new_caches["head"].append(nc)
        extras_out["head"].append(ex)

    if n_groups:
        scan_kinds = [kinds[n_head + j] for j in range(pat)]

        def body(carry, xs):
            xc = carry
            pp, cc = xs
            ncs, exs = [], []
            for j in range(pat):
                c = cc[j] if use_cache else None
                xc, nc, ex = block_apply(pp[j], cfg, scan_kinds[j], xc,
                                         positions, mode, c, pos, cache_len)
                ncs.append(nc if nc is not None else {})
                exs.append(ex)
            xc = shardctx.constrain_act(xc)
            return xc, (tuple(ncs), tuple(exs))

        if mode == "full" and shardctx.current_remat():
            body = jax.checkpoint(body, prevent_cse=False)
        cc_in = caches["scan"] if use_cache else tuple({} for _ in range(pat))
        x, (scan_caches, scan_extras) = jax.lax.scan(
            body, x, (params["scan"], cc_in))
        new_caches["scan"] = scan_caches
        extras_out["scan"] = scan_extras
    else:
        new_caches["scan"] = tuple({} for _ in range(pat))
        extras_out["scan"] = tuple({} for _ in range(pat))

    tail_base = n_head + n_groups * pat
    for i in range(n_tail):
        c = caches["tail"][i] if use_cache else None
        x, nc, ex = block_apply(params["tail"][i], cfg, kinds[tail_base + i],
                                x, positions, mode, c, pos, cache_len)
        new_caches["tail"].append(nc)
        extras_out["tail"].append(ex)

    if mode == "full":
        new_caches = None
    return x, new_caches, extras_out


# ---------------------------------------------------------------------------
# LM wrapper (embeddings + stack + head), incl. stubbed modality frontends

def lm_init(key, cfg, stack_fn=stack_init) -> Params:
    """The whole LM's params. ``stack_fn(key, cfg, dtype)`` builds the
    decoder stack; the offload engine passes one that keeps each routed
    expert stack on the host (serving/engine.init_offload_params)."""
    dtype = dtype_of(cfg)
    k_emb, k_stack, k_head, k_fe = jax.random.split(key, 4)
    p: Params = {
        "tok_emb": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model),
                                      jnp.float32) * 0.02).astype(dtype),
        "final_ln": rms_norm_init(cfg.d_model, dtype),
        "stack": stack_fn(k_stack, cfg, dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k_head, cfg.d_model, cfg.vocab_size, dtype)
    if cfg.frontend is not None:
        p["frontend_proj"] = dense_init(k_fe, cfg.frontend_dim, cfg.d_model,
                                        dtype)
    return p


def embed(params, cfg, tokens, modality=None):
    """tokens: (B, S_text) int32; modality: (B, S_m, frontend_dim) or None.

    VLM early fusion: projected patch embeddings are prepended to the token
    embeddings (the frontend itself is stubbed per the assignment).
    """
    x = jnp.take(params["tok_emb"], tokens, axis=0)
    n_prefix = 0
    if modality is not None and cfg.frontend == "vision":
        m = jnp.einsum("bsf,fd->bsd", modality.astype(x.dtype),
                       params["frontend_proj"])
        x = jnp.concatenate([m, x], axis=1)
        n_prefix = modality.shape[1]
    return x, n_prefix


def unembed(params, cfg, x):
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    w = params["tok_emb"].T if cfg.tie_embeddings else params["head"]
    return jnp.einsum("btd,dv->btv", x, w).astype(jnp.float32)


def lm_apply(params, cfg, tokens, modality=None, mode: str = "full",
             caches=None, pos=None, cache_len: int = 0):
    x, n_prefix = embed(params, cfg, tokens, modality)
    b, t, _ = x.shape
    if mode == "decode":
        positions = jnp.full((b, 1), pos, jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    x, new_caches, extras = stack_apply(params["stack"], cfg, x, positions,
                                        mode, caches, pos, cache_len)
    logits = unembed(params, cfg, x)
    return logits, new_caches, extras, n_prefix


def collect_moe_aux(cfg, extras) -> jnp.ndarray:
    """Mean MoE load-balance loss across layers (0 if no MoE)."""
    losses = []
    for ex in extras["head"] + list(extras["tail"]):
        if "moe_aux" in ex:
            losses.append(ex["moe_aux"])
    for ex in extras["scan"]:
        if isinstance(ex, dict) and "moe_aux" in ex:
            losses.append(jnp.mean(ex["moe_aux"]))
    if not losses:
        return jnp.zeros((), jnp.float32)
    return jnp.mean(jnp.stack(losses))

"""Jit'd public wrappers for the Pallas kernels with backend selection.

backend:
  "jnp"     — the pure-jnp oracle (used on CPU / for the dry-run lowering)
  "pallas"  — Pallas in interpret mode (CPU-validated kernel body)
  "tpu"     — Pallas compiled for TPU (the deployment target)
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import expert_ffn as _effn
from repro.kernels import ref
from repro.kernels.flash_attention import flash_decode as _flash_pallas
from repro.kernels.topk_gating import topk_gating as _topk_pallas


def topk_gating(logits: jnp.ndarray, k: int, backend: str = "jnp"):
    if backend == "jnp":
        return ref.topk_gating_ref(logits, k)
    return _topk_pallas(logits, k, interpret=(backend != "tpu"))


def expert_ffn(x, weights, ids, pos, wg, wu, wd, backend: str = "jnp"):
    """Grouped expert FFN over slot buffers (kernels/expert_ffn.py).

    x: (N, D); weights: (N, k) router weights; ids: (U,) sorted distinct
    slot ids padded by repeating the last; pos: (N, k) each pair's
    position in ids; wg/wu: (S, D, F); wd: (S, F, D) -> (N, D) in x's
    dtype. backend "jnp" runs the jnp grouped twin, "pallas" the kernel
    in interpret mode, "tpu" the compiled kernel.
    """
    if backend == "jnp":
        y = _effn.grouped_expert_ffn_jnp(x, ids, wg, wu, wd)
    else:
        y = _effn.grouped_expert_ffn(x, ids, wg, wu, wd,
                                     interpret=(backend != "tpu"))
    return _effn.combine(y, pos, weights).astype(x.dtype)


def flash_decode(q, k, v, valid_len, backend: str = "jnp"):
    if backend == "jnp":
        return ref.flash_decode_ref(q, k, v, valid_len)
    return _flash_pallas(q, k, v, valid_len, interpret=(backend != "tpu"))


def paged_flash_decode(q, k_pool, v_pool, tables, pos, scale=None, dv=None,
                       backend: str = "jnp"):
    """Paged flash-decode over a block-table KV pool.

    q: (N, KVH, G, dk); pools: (num_blocks, BS, KVH, *); tables: (N, W);
    pos: (N,). ``v_pool=None`` is the shared-page (MLA latent) layout —
    V slices out of the K fetch, one page read. backend "jnp" runs the
    lax.scan flash twin (the off-TPU serving route — same online-softmax
    recurrence, no interpreter overhead); "pallas" runs the kernel body in
    interpret mode (the CI validation route); "tpu" compiles it.
    """
    from repro.kernels import paged_attention as pa
    if backend not in ("jnp", "pallas", "tpu"):
        raise ValueError(f"unknown paged_flash_decode backend {backend!r}")
    if backend == "jnp":
        return pa.paged_flash_decode_jnp(q, k_pool, v_pool, tables, pos,
                                         scale=scale, dv=dv)
    return pa.paged_flash_decode_pallas(q, k_pool, v_pool, tables, pos,
                                        scale=scale, dv=dv,
                                        interpret=(backend != "tpu"))


def ssd_chunk(c, b, xdt, a_cum, backend: str = "jnp"):
    from repro.kernels.ssd_chunk import ssd_chunk as _p, ssd_chunk_ref as _r
    if backend == "jnp":
        return _r(c, b, xdt, a_cum)
    return _p(c, b, xdt, a_cum, interpret=(backend != "tpu"))

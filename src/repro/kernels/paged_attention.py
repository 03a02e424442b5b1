"""Paged flash-decode: block-table attention reading KV pages in place.

One query token per *lane* against a block-paged KV pool — the read half of
the paged serving engine. The grid is ``(lanes, kv_heads, table_width)``:
for each (lane, head) the innermost axis walks the lane's block table in
logical order, and a ``PrefetchScalarGridSpec`` index map turns the table
entry into the pool block to fetch, so K/V pages stream from the
``(num_blocks, block_size, KVH, hd)`` pool directly — the
``(N, W*block_size, ...)`` contiguous copy of the gather path is never
materialised. The kernel sees the pool as ``(num_blocks, block_size,
KVH*hd)`` (a free reshape) and fetches one head's ``(block_size, hd)``
page: a block's last two dims must be multiples of (8, 128) or the whole
array dims, which a ``(.., 1, hd)`` block over a KVH > 1 axis is not. So on
a TPU a pool with KVH > 1 needs ``hd % 128 == 0``; KVH == 1 (the MLA
latent pool) takes any width. Online softmax (running max / sum / accumulator in VMEM
scratch, rescaled per block) makes the walk single-pass; positions
``>= pos+1`` are masked, which also neutralises the scratch block 0 and any
unreferenced pool block a scratch-padded table names (their logical
positions always exceed ``pos``).

Two layouts share the one kernel:
  * GQA:  q ``(N, KVH, G, hd)`` against separate K and V pools.
  * MLA:  the absorbed decode is a single-"kv-head" attend where K is the
    whole ``(c, r)`` latent page and V is its first ``kv_lora_rank``
    features — pass ``v_pool=None`` with ``dv=rank`` and the kernel slices
    V out of the fetched K tile (one DMA per page, no second fetch, no
    concat).

``paged_flash_decode_jnp`` is the lax.scan twin of the kernel — identical
blockwise online-softmax recurrence, gathering at most ``tile_blocks``
table entries per step so off-TPU serving doesn't pay interpreter overhead.
Its live tile is the whole ``(N, W*BS, ...)`` copy whenever the table is
narrower than one tile (short/medium contexts — unavoidable without a real
kernel); past that the copy stays capped at ``tile_blocks`` blocks while
the gather route's keeps growing. ``kernels/ref.py::paged_flash_decode_ref``
is the dense oracle both are pinned against.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG = -1e30

# table entries walked per jnp-twin scan step: big enough that the einsum
# dominates the loop overhead, small enough that the live KV tile stays
# O(tile * block_size) positions instead of the full sequence
JNP_TILE_BLOCKS = 128


def _online_step(pos_ref, q_ref, o_ref, m_ref, l_ref, acc_ref, k, v, *,
                 block_size: int, scale: float):
    """Shared online-softmax body: one (lane, kv-head, block) grid step.
    k: (BS, dk), v: (BS, dv) — already loaded by the caller."""
    lane = pl.program_id(0)
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, dk)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G,BS)
    kpos = w * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos <= pos_ref[lane], s, NEG)

    m_prev = m_ref[...]                              # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.exp(s - m_new)                           # (G, BS)
    alpha = jnp.exp(m_prev - m_new)                  # (G, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(w == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_size: int, scale: float,
            dv: int):
    k = k_ref[0].astype(jnp.float32)                 # (BS, dk)
    v = v_ref[0][:, :dv].astype(jnp.float32)         # (BS, dv)
    _online_step(pos_ref, q_ref, o_ref, m_ref, l_ref, acc_ref, k, v,
                 block_size=block_size, scale=scale)


def _kernel_shared(tables_ref, pos_ref, q_ref, k_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_size: int, scale: float,
                   dv: int):
    # MLA latent layout: V is the leading ``dv`` features of the K tile —
    # one page fetch feeds both dots
    k = k_ref[0].astype(jnp.float32)                 # (BS, dk)
    _online_step(pos_ref, q_ref, o_ref, m_ref, l_ref, acc_ref, k,
                 k[:, :dv], block_size=block_size, scale=scale)


@partial(jax.jit, static_argnames=("scale", "dv", "interpret"))
def paged_flash_decode_pallas(q, k_pool, v_pool, tables, pos,
                              scale: Optional[float] = None,
                              dv: Optional[int] = None,
                              interpret: Optional[bool] = None):
    """q: (N, KVH, G, dk); k_pool/v_pool: (num_blocks, BS, KVH, *);
    tables: (N, W) int32; pos: (N,) int32 -> (N, KVH, G, dv).

    ``v_pool=None`` is the shared-page layout (MLA latents): V is sliced
    out of the fetched K tile, one DMA per page. ``dv`` selects the leading
    value features of the V tile (``kv_lora_rank`` for MLA); ``scale``
    overrides the ``dk**-0.5`` score scale (MLA scales by the materialised
    head dim, not the latent dim). ``interpret=None`` auto-resolves:
    compiled on TPU, interpreted elsewhere.
    """
    n, kvh, g, dk = q.shape
    bs = k_pool.shape[1]
    w = tables.shape[1]
    dvp = k_pool.shape[-1] if v_pool is None else v_pool.shape[-1]
    dv = dvp if dv is None else dv
    scale = dk ** -0.5 if scale is None else scale

    def page(pool):
        # (nb, BS, KVH, d) -> (nb, BS, KVH*d): head j's page is lane block j
        return pool.reshape(pool.shape[:2] + (-1,))

    in_specs = [
        pl.BlockSpec((1, 1, g, dk), lambda i, j, k, t, p: (i, j, 0, 0)),
        pl.BlockSpec((1, bs, dk), lambda i, j, k, t, p: (t[i, k], 0, j)),
    ]
    operands = [q, page(k_pool)]
    if v_pool is None:
        body = _kernel_shared
    else:
        body = _kernel
        in_specs.append(pl.BlockSpec((1, bs, dvp),
                                     lambda i, j, k, t, p: (t[i, k], 0, j)))
        operands.append(page(v_pool))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                        # tables, pos
        grid=(n, kvh, w),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda i, j, k, t, p: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),          # running max
            pltpu.VMEM((g, 1), jnp.float32),          # running sum
            pltpu.VMEM((g, dv), jnp.float32),         # output accumulator
        ],
    )
    return pl.pallas_call(
        partial(body, block_size=bs, scale=scale, dv=dv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, kvh, g, dv), q.dtype),
        interpret=resolve_interpret(interpret),
    )(tables.astype(jnp.int32), pos.astype(jnp.int32), *operands)


@partial(jax.jit, static_argnames=("scale", "dv", "tile_blocks"))
def paged_flash_decode_jnp(q, k_pool, v_pool, tables, pos,
                           scale: Optional[float] = None,
                           dv: Optional[int] = None,
                           tile_blocks: int = JNP_TILE_BLOCKS):
    """lax.scan twin of the Pallas kernel (same shapes, same recurrence).

    Each scan step gathers at most ``tile_blocks`` table entries per lane
    and applies the identical online-softmax update the kernel applies per
    block, so masked positions (pads, scratch, unreferenced blocks)
    contribute exactly zero in both. The live tile IS the full
    ``(N, W*BS, ...)`` copy while the table fits one tile; past
    ``tile_blocks`` blocks it stays capped while the gather route's copy
    keeps growing. ``v_pool=None`` is the shared-page (MLA latent) layout:
    V slices out of the gathered K tile, halving the gather traffic.
    """
    n, kvh, g, dk = q.shape
    bs = k_pool.shape[1]
    dvp = k_pool.shape[-1] if v_pool is None else v_pool.shape[-1]
    dv = dvp if dv is None else dv
    scale = dk ** -0.5 if scale is None else scale

    w = tables.shape[1]
    tile = min(tile_blocks, w)
    padw = (-w) % tile
    if padw:                                          # scratch-pad: masked
        tables = jnp.pad(tables, ((0, 0), (0, padw)))
    tiled = tables.reshape(n, -1, tile)               # (N, WT, tile)
    qf = q.astype(jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        wi, bids = xs                                 # bids: (N, tile)
        k = jnp.take(k_pool, bids.reshape(-1), axis=0).reshape(
            n, tile * bs, kvh, dk)
        if v_pool is None:
            v = k[..., :dv]
        else:
            v = jnp.take(v_pool, bids.reshape(-1), axis=0).reshape(
                n, tile * bs, kvh, dvp)[..., :dv]
        s = jnp.einsum("njgd,nsjd->njgs", qf, k.astype(jnp.float32)) * scale
        kpos = wi * tile * bs + jnp.arange(tile * bs)
        s = jnp.where(kpos[None, None, None, :] <= pos[:, None, None, None],
                      s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + jnp.einsum("njgs,nsjd->njgd", p,
                                       v.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((n, kvh, g, 1), NEG, jnp.float32)
    l0 = jnp.zeros((n, kvh, g, 1), jnp.float32)
    a0 = jnp.zeros((n, kvh, g, dv), jnp.float32)
    (_, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.arange(tiled.shape[1]), jnp.moveaxis(tiled, 1, 0)))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

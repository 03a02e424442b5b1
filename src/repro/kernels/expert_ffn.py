"""Grouped expert SwiGLU FFN over the tier-0 slot buffer.

One call serves N units (decode lanes or the tokens of a prefill chunk),
each routed to k experts that sit in rows of the ``(slots, D, F)`` slot
buffers. The host hands over the sorted distinct slot ids the call routes
to, padded to ``U = N * k`` by repeating the last real id, and for each
(unit, k) pair its position in that list. Each distinct slot's weights
are read once, in place, and multiplied against all N units:
``Y[u] = FFN_{ids[u]}(x)``, ``(U, N, D)`` float32. Each unit then sums its
own k rows of Y with its router weights, in router order, so a unit's
output does not depend on which other units share the call.

Grid: ``(U, F / BF)``. A ``PrefetchScalarGridSpec`` index map turns
``ids[u]`` into the slot row to fetch, so the weights stream from the
slot buffers with no gathered copy. The N activations stay in VMEM. A pad
step (an id equal to the one before it) keeps the previous step's block
index, so the pipeline issues no DMA for it, and it computes nothing.

Precision: the gate and up products take the weights' dtype on both
sides with float32 accumulation. For the down projection ``silu(g) * u``
stays float32: against bfloat16 weights it is split into three bfloat16
terms that together carry its whole float32 mantissa, stacked as rows of
one matmul, so each product is the one a float32 multiply would give.

``grouped_expert_ffn_jnp`` is the same grouped formulation in jnp (the
off-TPU serving route); ``ref.expert_ffn_ref`` stays the per-pair float32
oracle both are pinned against.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

F32 = jnp.float32


def _f32_terms(h, dtype):
    """Terms of ``dtype`` whose float32 sum is ``h``: one for float32,
    three for bfloat16 (8 + 8 + 8 bits of mantissa)."""
    if jnp.dtype(dtype) == jnp.dtype(F32):
        return [h]
    terms = []
    for _ in range(3):
        t = h.astype(dtype)
        terms.append(t)
        h = h - t.astype(F32)
    return terms


def _is_pad(ids_ref, u):
    return (u > 0) & (ids_ref[u] == ids_ref[jnp.maximum(u - 1, 0)])


def _kernel(ids_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    u = pl.program_id(0)
    fb = pl.program_id(1)

    @pl.when(fb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(jnp.logical_not(_is_pad(ids_ref, u)))
    def _compute():
        x = x_ref[...]                                   # (N, D)
        g = jnp.dot(x, wg_ref[0], preferred_element_type=F32)   # (N, BF)
        up = jnp.dot(x, wu_ref[0], preferred_element_type=F32)
        h = g * jax.nn.sigmoid(g) * up                   # silu(g) * u
        terms = _f32_terms(h, wd_ref.dtype)
        n = h.shape[0]
        y = jnp.dot(jnp.concatenate(terms, axis=0), wd_ref[0],
                    preferred_element_type=F32)          # (T*N, D)
        acc = y[:n]
        for i in range(1, len(terms)):
            acc = acc + y[i * n:(i + 1) * n]
        o_ref[0] += acc


def _block_f(f: int, block_f: Optional[int]) -> int:
    """The largest 128-multiple divisor of f that is <= block_f, else f
    (a (D, BF) tile's lane dim must be a multiple of 128 or all of f)."""
    if block_f is None or block_f >= f:
        return f
    return next((b for b in range(block_f - block_f % 128, 0, -128)
                 if f % b == 0), f)


@partial(jax.jit, static_argnames=("block_f", "interpret"))
def grouped_expert_ffn(x, ids, wg, wu, wd, block_f: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """x: (N, D); ids: (U,) int32 sorted distinct slot ids, padded by
    repeating the last; wg/wu: (S, D, F); wd: (S, F, D) -> (U, N, D) f32.

    Rows of Y at pad steps are zero. ``block_f=None`` reads a whole
    expert per grid step; ``interpret=None`` compiles on TPU and
    interprets elsewhere.
    """
    n, d = x.shape
    f = wg.shape[2]
    bf = _block_f(f, block_f)
    nf = f // bf

    def fblock(u, fb, ids):
        # a pad step names the block the step before it ended on
        return jnp.where(_is_pad(ids, u), nf - 1, fb)

    in_specs = [
        pl.BlockSpec((n, d), lambda u, fb, ids: (0, 0)),
        pl.BlockSpec((1, d, bf), lambda u, fb, ids: (ids[u], 0,
                                                     fblock(u, fb, ids))),
        pl.BlockSpec((1, d, bf), lambda u, fb, ids: (ids[u], 0,
                                                     fblock(u, fb, ids))),
        pl.BlockSpec((1, bf, d), lambda u, fb, ids: (ids[u],
                                                     fblock(u, fb, ids), 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ids.shape[0], nf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, d), lambda u, fb, ids: (u, 0, 0)),
    )
    # the three weight tiles, double-buffered, plus room for the
    # activations, the output block and the matmuls' temporaries
    tile_bytes = 3 * d * bf * wg.dtype.itemsize
    vmem = 2 * tile_bytes + 16 * n * (d + bf) * 4 + (16 << 20)
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((ids.shape[0], n, d), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=resolve_interpret(interpret),
    )(ids.astype(jnp.int32), x, wg, wu, wd)


@jax.jit
def grouped_expert_ffn_jnp(x, ids, wg, wu, wd):
    """The grouped formulation in jnp: take the U slots once, then einsum
    every unit against each, in float32. Same shapes as
    ``grouped_expert_ffn``; pad rows repeat the last slot's."""
    xf = x.astype(F32)
    g = jnp.einsum("nd,udf->unf", xf, jnp.take(wg, ids, 0).astype(F32))
    up = jnp.einsum("nd,udf->unf", xf, jnp.take(wu, ids, 0).astype(F32))
    h = jax.nn.silu(g) * up
    return jnp.einsum("unf,ufd->und", h, jnp.take(wd, ids, 0).astype(F32))


def combine(y, pos, weights):
    """y: (U, N, D) f32; pos: (N, k) positions into U; weights: (N, k)
    -> (N, D) f32: each unit's k expert outputs summed with its router
    weights in router order."""
    n, k = pos.shape
    rows = y[pos, jnp.arange(n)[:, None]]                # (N, k, D)
    w = weights.astype(F32)
    acc = w[:, 0, None] * rows[:, 0]
    for j in range(1, k):
        acc = acc + w[:, j, None] * rows[:, j]
    return acc


def pack_slots(slot_idx: np.ndarray, n_real: int) -> np.ndarray:
    """Host side of a call: slot_idx (N, k) holds each (unit, k) pair's
    slot, only the first ``n_real`` units live. Returns one int32 array,
    ``ids`` (U = N * k: the sorted distinct live slots, padded by
    repeating the last) then ``pos`` (N * k: each pair's position in
    ``ids``; pad units point at position 0, so they add no slot)."""
    n, k = slot_idx.shape
    live = slot_idx[:n_real]
    ids = np.unique(live)
    meta = np.zeros(2 * n * k, np.int32)
    meta[:ids.size] = ids
    meta[ids.size:n * k] = ids[-1]
    meta[n * k:n * k + live.size] = np.searchsorted(ids, live).ravel()
    return meta


def unpack_slots(meta, n: int, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``pack_slots``'s array -> (ids (U,), pos (N, k))."""
    return meta[:n * k], meta[n * k:].reshape(n, k)

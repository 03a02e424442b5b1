"""Cached-expert SwiGLU FFN kernel — the batch-1 decode compute the paper's
prefetcher keeps fed.

Grid: (k experts, F/BF ffn blocks). Each step loads one expert's
(D, BF)+(D, BF)+(BF, D) weight tiles from the slot buffer into VMEM, runs
the gated matmuls on the MXU (D and BF are 128-multiples by construction),
and accumulates ``weights[k] *`` partial output into the (1, D) out tile.
The x vector stays resident in VMEM across all grid steps.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, wg_ref, wu_ref, wd_ref, o_ref):
    ke = pl.program_id(0)
    fb = pl.program_id(1)

    @pl.when((ke == 0) & (fb == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                   # (1, D)
    wg = wg_ref[0].astype(jnp.float32)                   # (D, BF)
    wu = wu_ref[0].astype(jnp.float32)
    wd = wd_ref[0].astype(jnp.float32)                   # (BF, D)
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (g * jax.nn.sigmoid(g)) * u                      # silu(g) * u
    y = jnp.dot(h, wd, preferred_element_type=jnp.float32)
    o_ref[...] += w_ref[0, ke] * y


@partial(jax.jit, static_argnames=("block_f", "interpret"))
def expert_ffn(x, weights, wg, wu, wd, block_f: int = 512,
               interpret: bool = True):
    """x: (D,); weights: (k,); wg/wu: (k,D,F); wd: (k,F,D) -> (D,)."""
    k, d, f = wg.shape
    # the (D, BF) tiles' lane dim must be a multiple of 128 or all of f:
    # the largest 128-multiple divisor of f that is <= block_f, else f
    bf = next((b for b in range(block_f - block_f % 128, 0, -128)
               if f % b == 0), f)

    out = pl.pallas_call(
        _kernel,
        grid=(k, f // bf),
        in_specs=[
            pl.BlockSpec((1, d), lambda ke, fb: (0, 0)),        # x
            # combine weights are read one scalar per expert: SMEM, since
            # a VMEM load at a dynamic lane index does not lower
            pl.BlockSpec((1, k), lambda ke, fb: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, d, bf), lambda ke, fb: (ke, 0, fb)),
            pl.BlockSpec((1, d, bf), lambda ke, fb: (ke, 0, fb)),
            pl.BlockSpec((1, bf, d), lambda ke, fb: (ke, fb, 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda ke, fb: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
    )(x[None, :], weights[None, :].astype(jnp.float32), wg, wu, wd)
    return out[0].astype(x.dtype)

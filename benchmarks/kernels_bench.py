"""Kernel microbenchmarks: jnp oracle vs Pallas-interpret correctness and
call latency (CPU timings are regression signals, not TPU predictions)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, n=20):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6  # us


def run(log=print):
    rng = np.random.default_rng(0)
    out = {}

    # router gating at DeepSeek-V2 scale
    logits = jnp.asarray(rng.normal(size=(1024, 160)), jnp.float32)
    us_ref = _time(lambda x: ref.topk_gating_ref(x, 6), logits)
    us_pal = _time(lambda x: ops.topk_gating(x, 6, backend="pallas"), logits)
    wr, ir = ref.topk_gating_ref(logits, 6)
    wp, ip = ops.topk_gating(logits, 6, backend="pallas")
    np.testing.assert_allclose(np.sort(wr), np.sort(wp), rtol=1e-4, atol=1e-6)
    out["topk_gating_ref_us"] = us_ref
    out["topk_gating_pallas_interp_us"] = us_pal

    # grouped expert FFN at Lite widths: a decode bucket of 4 lanes, top-6
    # over 12 slots of a 16-slot buffer
    from repro.kernels.expert_ffn import pack_slots, unpack_slots
    n, k, d, f, slots = 4, 6, 2048, 1408, 16
    idx = np.stack([rng.choice(12, k, replace=False)
                    for _ in range(n)]).astype(np.int32)
    ids, pos = unpack_slots(jnp.asarray(pack_slots(idx, n)), n, k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.random((n, k)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(slots, d, f)) * 0.02, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(slots, d, f)) * 0.02, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(slots, f, d)) * 0.02, jnp.float32)
    args = (x, w, ids, pos, wg, wu, wd)
    np.testing.assert_allclose(
        np.asarray(ops.expert_ffn(*args, backend="jnp")),
        np.asarray(ops.expert_ffn(*args, backend="pallas")),
        rtol=2e-3, atol=2e-4)
    out["expert_ffn_jnp_us"] = _time(
        lambda *a: ops.expert_ffn(*a, backend="jnp"), *args)
    out["expert_ffn_pallas_interp_us"] = _time(
        lambda *a: ops.expert_ffn(*a, backend="pallas"), *args)

    # flash decode at 32k cache
    s, kvh, g, hd = 32768, 8, 4, 128
    q = jnp.asarray(rng.normal(size=(kvh * g, hd)), jnp.bfloat16)
    kk = jnp.asarray(rng.normal(size=(s, kvh, hd)), jnp.bfloat16)
    vv = jnp.asarray(rng.normal(size=(s, kvh, hd)), jnp.bfloat16)
    np.testing.assert_allclose(
        np.asarray(ref.flash_decode_ref(q, kk, vv, s), np.float32),
        np.asarray(ops.flash_decode(q, kk, vv, s, backend="pallas"),
                   np.float32), rtol=3e-2, atol=3e-2)
    out["flash_decode_ref_us"] = _time(
        lambda *a: ref.flash_decode_ref(*a), q, kk, vv, s)
    out["flash_decode_pallas_interp_us"] = _time(
        lambda *a: ops.flash_decode(*a, backend="pallas"), q, kk, vv, s)

    for kname, v in out.items():
        log(f"  {kname} = {v:.1f}us")
    return out

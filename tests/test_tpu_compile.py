"""The serving path's kernels compiled for a TPU v5e at published widths.

Nothing runs: the chip is described (``jax.experimental.topologies``), not
attached, and XLA's TPU compiler either accepts each program or raises what
the chip's compiler would — tiling rules interpret mode never checks. Every
compile must contain the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, so under several
pytest workers only the worker running this file may touch it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.deepseek_v2_lite import CONFIG as DSV2_LITE
from repro.kernels import ops
from repro.models import transformer as T

BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("block_size", [8, 16])
def test_mla_paged_decode_compiles(one_chip, block_size):
    # DeepSeek-V2-Lite's absorbed MLA decode: 16 heads over a 512+64 latent
    m = DSV2_LITE.mla
    lat = m.kv_lora_rank + m.rope_head_dim

    def f(q, pool, tables, pos):
        return ops.paged_flash_decode(
            q, pool, None, tables, pos,
            scale=(m.nope_head_dim + m.rope_head_dim) ** -0.5,
            dv=m.kv_lora_rank, backend="tpu")

    text = _compiled_text(f, one_chip, ((4, 1, 16, lat), BF16),
                          ((64, block_size, 1, lat), BF16),
                          ((4, 36), I32), ((4,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block_size", [8, 16])
def test_gqa_paged_decode_compiles(one_chip, block_size):
    # GQA with 8 KV heads of width 128 (4 query heads each)
    def f(q, kp, vp, tables, pos):
        return ops.paged_flash_decode(q, kp, vp, tables, pos, backend="tpu")

    pool = ((64, block_size, 8, 128), BF16)
    text = _compiled_text(f, one_chip, ((4, 8, 4, 128), BF16), pool, pool,
                          ((4, 36), I32), ((4,), I32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,slots", [(4, 104), (8, 256)])
def test_expert_ffn_compiles(one_chip, n, slots):
    # the grouped expert FFN of a decode bucket of 4 (U = 24, the offload
    # cell's 104 slots) and of an 8-token prefill chunk (U = 48, the
    # resident cell's 256): DeepSeek-V2-Lite's d_model 2048, d_ff 1408,
    # a whole expert per grid step under the kernel's VMEM limit
    from repro.kernels.expert_ffn import unpack_slots
    d, f = DSV2_LITE.d_model, DSV2_LITE.moe.d_ff_expert
    k = DSV2_LITE.moe.top_k
    u = n * k

    def g(x, w, slots_, wg, wu, wd):
        ids, pos = unpack_slots(slots_, n, k)
        return ops.expert_ffn(x, w, ids, pos, wg, wu, wd, backend="tpu")

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
        ((n, d), BF16), ((n, k), BF16), ((2 * u,), I32),
        ((slots, d, f), BF16), ((slots, d, f), BF16), ((slots, f, d), BF16))]
    compiled = jax.jit(g).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the slots are read in place: no gathered weight copy, not even one
    # expert's, is staged in device memory
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * d * f * 2


def test_mla_layer_paged_step_compiles(one_chip):
    # the serving engine's whole paged attention program for one MLA layer
    # (projections, latent scatter, kernel read) at published widths
    cfg = DSV2_LITE
    lp = jax.eval_shape(
        lambda k: T.block_init(k, cfg, "mla", False, BF16),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda: T.block_paged_cache_init(cfg, "mla", 64, 8, 5, BF16))

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)

    lp, cache = jax.tree.map(place, (lp, cache))
    x = jax.ShapeDtypeStruct((4, 1, cfg.d_model), BF16, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((4, 36), I32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((4,), I32, sharding=one_chip)

    def step(lp, x, cache, tables, pos):
        return T.block_paged_decode(lp, cfg, "mla", x, cache, tables, pos,
                                    kernel="tpu")

    text = jax.jit(step).lower(lp, x, cache, tables, pos).compile().as_text()
    assert "tpu_custom_call" in text

"""Pallas kernel validation: interpret-mode kernel body vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("t,e,k", [
    (7, 16, 2), (64, 64, 6), (33, 160, 6), (256, 128, 1), (4, 8, 8),
    (130, 100, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_gating(t, e, k, dtype):
    logits = jnp.asarray(RNG.normal(size=(t, e)) * 2, dtype)
    wr, ir = ref.topk_gating_ref(logits, k)
    wp, ip = ops.topk_gating(logits, k, backend="pallas")
    np.testing.assert_allclose(np.sort(np.asarray(wr)), np.sort(np.asarray(wp)),
                               rtol=2e-3, atol=1e-5)
    for row in range(t):
        assert set(np.asarray(ir)[row].tolist()) == \
            set(np.asarray(ip)[row].tolist()), row
    # weights sum to 1 after renormalisation
    np.testing.assert_allclose(np.asarray(wp).sum(-1), 1.0, atol=1e-3)


def _routing(n, n_real, k, slots, n_experts, seed):
    """(n, k) slot ids: each live unit draws k distinct slots out of
    ``n_experts`` rows of a buffer of ``slots``, so units share slots;
    pad units (past n_real) hold slot 0, which no live unit may use."""
    rng = np.random.default_rng(seed)
    pool = rng.choice(np.arange(1, slots), n_experts, replace=False)
    idx = np.zeros((n, k), np.int32)
    for i in range(n_real):
        idx[i] = pool[rng.choice(n_experts, k, replace=False)]
    return idx


@pytest.mark.parametrize("n,n_real,k,d,f,slots,n_experts,block_f", [
    (1, 1, 2, 128, 128, 4, 3, None),      # one decode lane
    (4, 4, 6, 256, 512, 16, 8, None),     # duplicates across units
    (4, 2, 2, 128, 256, 8, 4, None),      # two pad units, no live pairs
    (8, 8, 6, 128, 1408, 48, 12, None),   # DSV2-Lite's expert width
    (8, 5, 2, 128, 1408, 24, 6, 128),     # 11 128-wide F tiles, pads
    (2, 2, 2, 128, 256, 256, 3, None),    # buffer far larger than U
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_ffn(n, n_real, k, d, f, slots, n_experts, block_f, dtype):
    """The grouped kernel (interpret mode) against the per-pair float32
    oracle: each live unit's k experts gathered and run one by one."""
    from repro.kernels.expert_ffn import (combine, grouped_expert_ffn,
                                          pack_slots, unpack_slots)
    idx = _routing(n, n_real, k, slots, n_experts, seed=n * 131 + f)
    x = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    w = jnp.asarray(RNG.random((n, k)) + 0.1, dtype)
    wg = jnp.asarray(RNG.normal(size=(slots, d, f)) * 0.05, dtype)
    wu = jnp.asarray(RNG.normal(size=(slots, d, f)) * 0.05, dtype)
    wd = jnp.asarray(RNG.normal(size=(slots, f, d)) * 0.05, dtype)
    meta = pack_slots(idx, n_real)
    ids, pos = unpack_slots(jnp.asarray(meta), n, k)
    distinct = np.unique(idx[:n_real])
    np.testing.assert_array_equal(np.asarray(ids)[:distinct.size], distinct)
    assert np.all(np.asarray(ids)[distinct.size:] == distinct[-1])
    y = grouped_expert_ffn(x, ids, wg, wu, wd, block_f=block_f,
                           interpret=True)
    # pad steps compute nothing
    assert not np.asarray(y)[distinct.size:].any()
    yp = np.asarray(combine(y, pos, w).astype(dtype), np.float32)[:n_real]
    yr = np.stack([np.asarray(ref.expert_ffn_ref(
        x[i], w[i], wg[idx[i]], wu[idx[i]], wd[idx[i]]), np.float32)
        for i in range(n_real)])
    tol = 5e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(yr, yp, rtol=tol, atol=tol)


@pytest.mark.parametrize("s,kvh,g,hd,vl", [
    (128, 2, 4, 64, 100), (1024, 1, 16, 128, 1024), (96, 4, 1, 32, 50),
    (2048, 8, 2, 64, 1500), (512, 1, 1, 128, 1),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(s, kvh, g, hd, vl, dtype):
    h = kvh * g
    q = jnp.asarray(RNG.normal(size=(h, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(s, kvh, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(s, kvh, hd)), dtype)
    yr = np.asarray(ref.flash_decode_ref(q, k, v, vl), np.float32)
    yp = np.asarray(ops.flash_decode(q, k, v, vl, backend="pallas"),
                    np.float32)
    tol = 5e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(yr, yp, rtol=tol, atol=tol)


def test_flash_decode_matches_model_attention():
    """The kernel must agree with the model's decode attention math."""
    from repro.configs import get_reduced
    cfg = get_reduced("yi-6b")
    s, kvh, hd, h = 64, cfg.num_kv_heads, cfg.hd, cfg.num_heads
    q = jnp.asarray(RNG.normal(size=(h, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(s, kvh, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(s, kvh, hd)), jnp.float32)
    out = ops.flash_decode(q, k, v, 40, backend="pallas")
    ref_out = ref.flash_decode_ref(q, k, v, 40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Paged flash-decode: block-table attention against the pool layout


def _paged_case(bs, kvh, g, hd, w, n, seed, dtype=jnp.float32, pad_w=0):
    """Random pool + per-lane tables/positions. Lane tables draw distinct
    blocks (plus ``pad_w`` scratch-padded tail entries); positions are
    ragged and include a partially-filled last block."""
    rng = np.random.default_rng(seed)
    nb = n * w + 3                       # spare blocks stay unreferenced
    q = jnp.asarray(rng.normal(size=(n, kvh, g, hd)), dtype)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), dtype)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), dtype)
    perm = rng.permutation(nb - 1)[: n * w] + 1          # never scratch
    tables = np.zeros((n, w + pad_w), np.int32)
    tables[:, :w] = perm.reshape(n, w)
    # ragged: lane 0 ends mid-block, last lane uses the full table
    pos = rng.integers(0, w * bs, size=n)
    pos[0] = (w - 1) * bs + bs // 2 - 1 if w * bs > 1 else 0
    pos[-1] = w * bs - 1
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("bs", [8, 16, 64])
@pytest.mark.parametrize("kvh,g", [(1, 4), (2, 2), (4, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_flash_decode(bs, kvh, g, dtype):
    """Kernel body (interpret) and its lax.scan twin vs the dense oracle,
    across block sizes, GQA group sizes, ragged pos, and a scratch-padded
    table tail."""
    q, kp, vp, tables, pos = _paged_case(bs, kvh, g, hd=32, w=3, n=3,
                                         seed=bs * 10 + kvh, dtype=dtype,
                                         pad_w=2)
    yr = np.asarray(ref.paged_flash_decode_ref(q, kp, vp, tables, pos),
                    np.float32)
    tol = 5e-4 if dtype == jnp.float32 else 3e-2
    for backend in ("pallas", "jnp"):
        yp = np.asarray(ops.paged_flash_decode(q, kp, vp, tables, pos,
                                               backend=backend), np.float32)
        np.testing.assert_allclose(yr, yp, rtol=tol, atol=tol,
                                   err_msg=backend)


def test_paged_flash_decode_mla_layout():
    """The MLA latent layout (``v_pool=None``): one kv head, K = the whole
    latent page, V = its first ``dv`` features sliced from the same fetch,
    custom scale — and it must equal passing the pool explicitly twice."""
    q, kp, _, tables, pos = _paged_case(bs=8, kvh=1, g=4, hd=48, w=4, n=2,
                                        seed=7)
    scale, dv = 0.125, 32
    yr = np.asarray(ref.paged_flash_decode_ref(q, kp, None, tables, pos,
                                               scale=scale, dv=dv),
                    np.float32)
    assert yr.shape == (2, 1, 4, dv)
    y2 = np.asarray(ref.paged_flash_decode_ref(q, kp, kp, tables, pos,
                                               scale=scale, dv=dv),
                    np.float32)
    np.testing.assert_array_equal(yr, y2)    # shared == explicit two-pool
    for backend in ("pallas", "jnp"):
        yp = np.asarray(ops.paged_flash_decode(q, kp, None, tables, pos,
                                               scale=scale, dv=dv,
                                               backend=backend), np.float32)
        np.testing.assert_allclose(yr, yp, rtol=5e-4, atol=5e-4,
                                   err_msg=backend)


def test_paged_flash_decode_jnp_tiling_invariant():
    """The scan twin's tile size is a perf knob, not a semantics knob."""
    from repro.kernels.paged_attention import paged_flash_decode_jnp
    q, kp, vp, tables, pos = _paged_case(bs=8, kvh=2, g=2, hd=32, w=5, n=2,
                                         seed=3)
    base = np.asarray(paged_flash_decode_jnp(q, kp, vp, tables, pos,
                                             tile_blocks=1), np.float32)
    for tile in (2, 3, 5, 128):
        got = np.asarray(paged_flash_decode_jnp(q, kp, vp, tables, pos,
                                                tile_blocks=tile),
                         np.float32)
        np.testing.assert_allclose(base, got, rtol=1e-5, atol=1e-6,
                                   err_msg=f"tile={tile}")


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_paged_flash_decode_scratch_invariance(backend):
    """Output is invariant to the contents of the scratch block and of pool
    blocks no table references below ``pos`` — masked positions contribute
    exactly zero (the hypothesis sweep in test_properties.py randomises
    this; here one deterministic case pins both backends)."""
    rng = np.random.default_rng(0)
    bs, kvh, g, hd, nb = 8, 2, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(2, kvh, g, hd)), jnp.float32)
    kp = np.asarray(rng.normal(size=(nb, bs, kvh, hd)), np.float32)
    vp = np.asarray(rng.normal(size=(nb, bs, kvh, hd)), np.float32)
    tables = jnp.asarray([[3, 5], [6, 0]], jnp.int32)   # lane 1: scratch tail
    pos = jnp.asarray([15, 4], jnp.int32)
    kp2, vp2 = kp.copy(), vp.copy()
    for b in (0, 1, 2, 4, 7):                 # scratch + unreferenced
        kp2[b] = 99.0
        vp2[b] = -99.0
    out1 = np.asarray(ops.paged_flash_decode(
        q, jnp.asarray(kp), jnp.asarray(vp), tables, pos, backend=backend))
    out2 = np.asarray(ops.paged_flash_decode(
        q, jnp.asarray(kp2), jnp.asarray(vp2), tables, pos,
        backend=backend))
    np.testing.assert_array_equal(out1, out2)


@pytest.mark.parametrize("kernel", ["pallas", "jnp"])
def test_paged_attn_decode_kernel_vs_gather(kernel):
    """Model-level GQA pin: ``paged_attn_decode`` through the kernel route
    equals the gather + dense-attend reference route (yi-6b reduced:
    4 heads over 2 kv heads)."""
    from repro.configs import get_reduced
    from repro.models import attention as attn
    cfg = get_reduced("yi-6b")
    key = jax.random.PRNGKey(0)
    p = attn.attn_init(key, cfg, jnp.float32)
    bs, w, n = 8, 3, 3
    cache = attn.paged_init_cache(cfg, n * w + 1, bs, jnp.float32)
    cache = {k: jax.random.normal(jax.random.PRNGKey(1), v.shape, v.dtype)
             for k, v in cache.items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (n, 1, cfg.d_model))
    tables = jnp.asarray(1 + np.arange(n * w).reshape(n, w), jnp.int32)
    pos = jnp.asarray([5, 17, 23], jnp.int32)
    y_ref, c_ref = attn.paged_attn_decode(p, cfg, x, cache, tables, pos)
    y_ker, c_ker = attn.paged_attn_decode(p, cfg, x, cache, tables, pos,
                                          kernel=kernel)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ker),
                               rtol=2e-5, atol=2e-5)
    for k in c_ref:                      # scatter identical on both routes
        np.testing.assert_array_equal(np.asarray(c_ref[k]),
                                      np.asarray(c_ker[k]))


@pytest.mark.parametrize("kernel", ["pallas", "jnp"])
def test_mla_paged_kernel_vs_attend(kernel):
    """MLA pin: absorbed paged decode through the kernel equals the
    ``_mla_attend`` gather reference (deepseek-v2-lite reduced latents)."""
    from repro.configs import get_reduced
    from repro.models import mla
    cfg = get_reduced("deepseek-v2-lite")
    p = mla.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    bs, w, n = 8, 3, 2
    cache = mla.mla_paged_init_cache(cfg, n * w + 1, bs, jnp.float32)
    cache = {k: jax.random.normal(jax.random.PRNGKey(1), v.shape, v.dtype)
             for k, v in cache.items()}
    x = jax.random.normal(jax.random.PRNGKey(2), (n, 1, cfg.d_model))
    tables = jnp.asarray(1 + np.arange(n * w).reshape(n, w), jnp.int32)
    pos = jnp.asarray([11, 23], jnp.int32)
    y_ref, c_ref = mla.mla_paged_decode(p, cfg, x, cache, tables, pos)
    y_ker, c_ker = mla.mla_paged_decode(p, cfg, x, cache, tables, pos,
                                        kernel=kernel)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ker),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(c_ref["lat"]),
                                  np.asarray(c_ker["lat"]))


@pytest.mark.parametrize("g,h,l,n,p", [
    (4, 3, 32, 16, 64), (2, 8, 128, 128, 64), (6, 1, 64, 32, 32),
    (1, 24, 128, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk(g, h, l, n, p, dtype):
    """Mamba-2 SSD within-chunk kernel vs its oracle (and transitively the
    model's y_diag einsum, which the oracle mirrors)."""
    from repro.kernels.ssd_chunk import ssd_chunk, ssd_chunk_ref
    c = jnp.asarray(RNG.normal(size=(g, l, n)) * 0.3, dtype)
    b = jnp.asarray(RNG.normal(size=(g, l, n)) * 0.3, dtype)
    x = jnp.asarray(RNG.normal(size=(g, h, l, p)) * 0.5, dtype)
    a = jnp.asarray(-np.abs(RNG.normal(size=(g, h, l))).cumsum(-1) * 0.1,
                    jnp.float32)
    yr = np.asarray(ssd_chunk_ref(c, b, x, a), np.float32)
    yp = np.asarray(ssd_chunk(c, b, x, a), np.float32)
    tol = 5e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(yr, yp, rtol=tol, atol=tol)

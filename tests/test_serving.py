"""Serving engine: the offloaded layer-by-layer decode must be numerically
identical to the monolithic decode_step, and the cache accounting sane."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policies import NextLayerAllPolicy, NoPrefetchPolicy
from repro.core.tracing import moe_layer_ids
from repro.serving.engine import OffloadEngine
from repro.serving.offload import HostExpertStore, make_offload_cache

from helpers import tiny_backbone


@pytest.fixture(scope="module")
def backbone():
    return tiny_backbone()


def test_engine_matches_monolithic_decode(backbone):
    cfg, model, params, corpus = backbone
    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    engine = OffloadEngine(model, params, policy=None, capacity=n_total)

    toks = [3, 17, 99, 255, 7, 42]
    state_ref = model.init_decode_state(1, 16)
    state_eng = engine.init_state(16)
    step_fn = jax.jit(model.decode_step)
    for t, tok in enumerate(toks):
        ref_logits, state_ref = step_fn(
            params, state_ref, {"tokens": jnp.full((1, 1), tok, jnp.int32)})
        eng_logits, state_eng, _ = engine.decode_token(state_eng, tok)
        np.testing.assert_allclose(eng_logits, np.asarray(ref_logits)[0],
                                   rtol=2e-4, atol=2e-4, err_msg=f"tok {t}")
    # full capacity: after first touch, everything hits
    assert engine.stats.hit_rate > 0.0


def test_engine_small_cache_misses_and_stalls(backbone):
    cfg, model, params, corpus = backbone
    engine = OffloadEngine(model, params, policy=NoPrefetchPolicy(),
                           capacity=2)
    state = engine.init_state(16)
    for tok in [3, 17, 99, 255]:
        engine.decode_token(state, tok)
    s = engine.stats
    assert s.misses > 0
    assert s.fetch_bytes > 0
    assert s.sim_stall_s > 0
    assert 0.0 <= s.hit_rate < 1.0


def test_engine_prefetch_all_reduces_misses(backbone):
    cfg, model, params, corpus = backbone
    e = cfg.moe.num_experts
    n_layers = len(moe_layer_ids(cfg))
    cap = max(2, (n_layers * e) // 2)

    eng_none = OffloadEngine(model, params, NoPrefetchPolicy(), cap)
    eng_all = OffloadEngine(model, params, NextLayerAllPolicy(e), cap)
    toks = [3, 17, 99, 255, 7, 42, 13, 5]
    s1, s2 = eng_none.init_state(16), eng_all.init_state(16)
    for tok in toks:
        l1, s1, _ = eng_none.decode_token(s1, tok)
        l2, s2, _ = eng_all.decode_token(s2, tok)
        # prefetching must never change the computed logits
        np.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-4)
    assert eng_all.stats.hit_rate >= eng_none.stats.hit_rate


def test_slot_buffer_mechanics(backbone):
    cfg, model, params, _ = backbone
    from repro.serving.engine import unstack_layers
    layers = unstack_layers(cfg, params)
    moe_layers = [layers[i]["moe"] for i in moe_layer_ids(cfg)]
    store = HostExpertStore(moe_layers)
    cache, buf = make_offload_cache(store, capacity=3)

    cache.access((0, 1))
    cache.access((0, 2))
    assert (0, 1) in buf.slot_of and (0, 2) in buf.slot_of
    wg, wu, wd = buf.gather([(0, 1), (0, 2)])
    np.testing.assert_allclose(np.asarray(wg[0]),
                               store.layers[0]["w_gate"][1])
    np.testing.assert_allclose(np.asarray(wd[1]),
                               store.layers[0]["w_down"][2])
    # eviction releases slots
    cache.access((1, 0))
    cache.access((1, 1))            # capacity 3 -> evicts (0,1)
    assert (0, 1) not in buf.slot_of
    assert len(buf.slot_of) == 3
    assert buf.fetch_count == 4


def test_engine_pallas_expert_backend(backbone):
    """The engine's expert compute via the Pallas kernel (interpret mode)
    must match the jnp backend — the TPU deployment path, exercised live."""
    cfg, model, params, _ = backbone
    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    eng_jnp = OffloadEngine(model, params, None, n_total,
                            expert_backend="jnp")
    eng_pal = OffloadEngine(model, params, None, n_total,
                            expert_backend="pallas")
    s1, s2 = eng_jnp.init_state(8), eng_pal.init_state(8)
    for tok in [3, 17, 99]:
        l1, s1, _ = eng_jnp.decode_token(s1, tok)
        l2, s2, _ = eng_pal.decode_token(s2, tok)
        np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_expert_from_slots_matches_per_pair(backbone, backend):
    """The engine's grouped expert program against the per-pair
    formulation it replaced: a weight copy gathered for every (unit, k)
    pair, the float32 oracle per unit, the shared experts added."""
    from repro.kernels import ref
    from repro.kernels.expert_ffn import pack_slots
    from repro.models.common import ffn_apply
    from repro.serving.engine import DecodeCore
    cfg, model, params, _ = backbone
    k = cfg.moe.top_k
    core = DecodeCore(model, params, 8, expert_backend=backend, max_batch=4)
    lp = core.layers[moe_layer_ids(cfg)[0]]
    rng = np.random.default_rng(3)
    s, d, f = core.slots.w_gate.shape
    bufs = [jnp.asarray(rng.normal(size=shape) * 0.05, jnp.float32)
            for shape in ((s, d, f), (s, d, f), (s, f, d))]
    h = jnp.asarray(rng.normal(size=(4, 1, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 1, d)), jnp.float32)
    w = jnp.asarray(rng.random((4, 1, k)), jnp.float32)
    slot_idx = np.array([[0, 5], [5, 2], [7, 0], [0, 0]], np.int32)[:, :k]
    n_real = 3
    out = np.asarray(core._expert(h, w, jnp.asarray(pack_slots(slot_idx,
                                                               n_real)),
                                  *bufs, lp["moe"].get("shared"), x))
    for i in range(n_real):
        g, u, dn = (b[slot_idx[i]] for b in bufs)
        want = x[i, 0] + ref.expert_ffn_ref(h[i, 0], w[i, 0], g, u, dn)
        if lp["moe"].get("shared") is not None:
            want = want + ffn_apply(lp["moe"]["shared"], h[i], "swiglu")[0]
        np.testing.assert_allclose(out[i, 0], np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_expert_counters_on_fixed_routing(backbone):
    """``expert.pairs`` counts the live (unit, k) pairs of a call and
    ``expert.distinct`` the slots it reads: pad units add neither."""
    from repro.serving.engine import DecodeCore
    from repro.serving.telemetry import Telemetry
    cfg, model, params, _ = backbone
    tel = Telemetry()
    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    core = DecodeCore(model, params, n_total, max_batch=4, telemetry=tel)
    lp = core.layers[moe_layer_ids(cfg)[0]]
    d = cfg.d_model
    idx = np.array([[0, 1], [1, 2], [3, 0], [9, 10]], np.int32)
    h = jnp.zeros((4, 1, d), jnp.float32)
    w = jnp.full((4, 1, 2), 0.5, jnp.float32)
    core._moe_units(0, lp, h, w, h, idx, n_real=3)
    assert tel.total("expert.pairs") == 6
    assert tel.total("expert.distinct") == 4       # experts 0-3, not 9, 10
    core._moe_units(0, lp, h, w, h, idx, n_real=1)
    assert tel.total("expert.pairs") == 8
    assert tel.total("expert.distinct") == 6


def test_host_only_routed_experts_match_decode_step():
    """``init_offload_params`` draws ``lm_init``'s tree with every routed
    expert stack on the host; an engine built from it keeps routed experts
    on the device only in tier-0 slots, and at full capacity its greedy
    streams equal the monolithic ``model.decode_step``'s."""
    from repro.configs import get_reduced
    from repro.models import build_model
    from repro.serving.engine import ROUTED, init_offload_params
    from repro.serving.scheduler import BatchedOffloadEngine

    cfg = get_reduced("deepseek-v2-lite")
    model = build_model(cfg)
    key = jax.random.PRNGKey(7)
    params = init_offload_params(cfg, key)
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        names = [getattr(k, "key", None) for k in path]
        routed = names[-1] in ROUTED and names[-2] == "moe"
        assert isinstance(leaf, np.ndarray) == routed, names
    # the same draw as the model's own init, layer for layer, up to one
    # rounding step of the leaf's dtype (the layers are drawn by a jitted
    # program, the model's init runs op by op)
    def same(a, b):
        eps = float(jnp.finfo(b.dtype).eps)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=eps,
                                   atol=eps * 1e-2)
    jax.tree.map(same, params, model.init(key))

    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    eng = BatchedOffloadEngine(model, params, None, n_total, max_batch=2)
    for lp in eng.core.layers:
        assert not set(ROUTED) & set(lp.get("moe", {}))
        assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(lp))
    prompts = [[3, 17, 5, 250, 9], [99, 7, 42]]
    max_new, cache_len = 5, 16
    got = eng.generate(prompts, max_new=max_new, cache_len=cache_len)

    dev = jax.device_put(params)
    step_fn = jax.jit(model.decode_step)
    for prompt, stream in zip(prompts, got):
        state = model.init_decode_state(1, cache_len)
        cur, ref = prompt[0], []
        for t in range(len(prompt) + max_new):
            logits, state = step_fn(
                dev, state, {"tokens": jnp.full((1, 1), cur, jnp.int32)})
            if t + 1 < len(prompt):
                cur = prompt[t + 1]
            else:
                cur = int(np.argmax(np.asarray(logits)[0]))
                ref.append(cur)
        assert stream == ref

"""Learned cache replacement + quantized cold tiers.

The predictor as *replacement policy*: a ReuseDistanceScorer maps the
multi-horizon prediction window to per-key predicted-next-use distances,
and both the tier-0 ExpertCache and the store's tier-1 cache evict the
unpinned key predicted furthest from reuse — degrading to exact LRU when
no prediction covers a candidate. Streams must stay token-identical
across policies. Cold tiers (2/3) optionally store int8: round-trip
error is bounded by half a quantization step per element, the ledger
invariants survive the new demote path, and the full-precision default
stays bit-exact.
"""
import numpy as np
import pytest

from repro.core.cache import ExpertCache
from repro.core.policies import (NextLayerAllPolicy, Policy,
                                 ReuseDistanceScorer)
from repro.core.tracing import moe_layer_ids
from repro.serving.expertstore import (TierConfig, TieredExpertStore)
from repro.serving.offload import (TIER_DISK, TIER_HOST, TIER_PEER,
                                   HostExpertStore)

from helpers import tiny_backbone
from test_expertstore import make_store_layers

PROMPTS = [[3, 17, 5], [99, 255, 7, 42], [13, 5], [21, 8, 9]]
MAX_NEW = 6
CACHE_LEN = 16


# ---------------------------------------------------------------------------
# ReuseDistanceScorer semantics

def test_scorer_record_tick_staleness():
    s = ReuseDistanceScorer()
    assert s.distance(("a")) is None             # nothing recorded
    s.record([("a")], distance=0)
    s.record([("b")], distance=2)
    assert s.distance(("a")) == 1 and s.distance(("b")) == 3
    s.tick()
    # a key whose predicted use has passed is stale, not imminent: the
    # just-computed layer's keys must look like the BEST victims
    assert s.distance(("a")) is None
    assert s.distance(("b")) == 2
    # a sooner prediction overwrites, a later one does not (keep the
    # soonest live estimate)
    s.record([("b")], distance=0)
    assert s.distance(("b")) == 1
    s.record([("b")], distance=5)
    assert s.distance(("b")) == 1
    s.reset()
    assert s.clock == 0 and s.distance(("b")) is None


def test_scorer_prunes_stale_entries():
    s = ReuseDistanceScorer()
    s.PRUNE_AT = 8
    s.record([(0, e) for e in range(10)], distance=0)
    s.tick()                                     # all 10 now stale
    s.record([(1, 0)], distance=3)
    s.tick()
    assert len(s._next_use) <= s.PRUNE_AT
    assert s.distance((1, 0)) == 3               # live entries survive


# ---------------------------------------------------------------------------
# tier-0 learned eviction

def test_learned_evicts_furthest_keeps_predicted_soon():
    s = ReuseDistanceScorer()
    c = ExpertCache(3, policy="learned", scorer=s)
    s.record([(0, 0)], distance=0)               # reuse imminent
    s.record([(0, 1)], distance=4)               # reuse far away
    c.access((0, 0))
    c.access((0, 1))
    c.access((9, 9))                             # no prediction at all
    c.access((5, 5))                             # forces one eviction
    assert (9, 9) not in c                       # unpredicted goes first
    c.access((6, 6))                             # second eviction: (5,5)
    assert (5, 5) not in c
    assert (0, 0) in c and (0, 1) in c           # predicted keys survive
    assert c.stats.evictions_learned == 2
    assert c.stats.evictions_lru == 0


def test_learned_requires_scorer():
    with pytest.raises(AssertionError):
        ExpertCache(2, policy="learned")


def test_learned_degrades_to_lru_and_never_evicts_pinned():
    """Property: with NO recorded predictions a learned cache makes
    exactly the LRU choices (same residents in the same recency order),
    and with arbitrary predictions pinned keys are never evicted."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    keys = [(0, e) for e in range(8)]
    ops = st.lists(
        st.tuples(
            st.sampled_from(["access", "prefetch", "pin", "unpin",
                             "record", "tick"]),
            st.sampled_from(keys),
            st.integers(min_value=0, max_value=4)),
        min_size=1, max_size=80)

    @settings(deadline=None, max_examples=60)
    @given(ops=ops, use_predictions=st.booleans())
    def run(ops, use_predictions):
        cap = 4
        scorer = ReuseDistanceScorer()
        learned = ExpertCache(cap, "learned", scorer=scorer)
        lru = ExpertCache(cap, "lru")
        pinned = set()
        for op, k, d in ops:
            if op == "access":
                learned.access(k)
                lru.access(k)
            elif op == "prefetch":
                learned.prefetch([k], horizon=d % 2)
                lru.prefetch([k], horizon=d % 2)
            elif op == "pin":
                # keep one slot always evictable so inserts can't dead-end
                if k in learned and k in lru and len(pinned | {k}) < cap:
                    learned.pin(k)
                    lru.pin(k)
                    pinned.add(k)
            elif op == "unpin":
                if k in pinned:
                    learned.unpin(k)
                    lru.unpin(k)
                    pinned.discard(k)
            elif op == "record" and use_predictions:
                scorer.record([k], distance=d)
            elif op == "tick" and use_predictions:
                scorer.tick()
            # pinned keys are NEVER evicted, predictions or not
            for p in pinned:
                assert p in learned and p in lru
        if not use_predictions:
            # no predictions ever recorded -> exact LRU behaviour
            assert list(learned._entries) == list(lru._entries)
            assert learned.stats.evictions == lru.stats.evictions
            assert learned.stats.evictions_learned == 0

    run()


# ---------------------------------------------------------------------------
# tier-1 learned eviction (TieredExpertStore cache)

def test_store_learned_shrink_keeps_predicted():
    layers = make_store_layers()
    scorer = ReuseDistanceScorer()
    tc = TierConfig(num_shards=3, shard_dram_experts=2, cache_experts=2)
    store = TieredExpertStore(layers, tc, scorer=scorer)
    slow = [k for k in sorted(store.home_shard)
            if store.tier_of(k) in (TIER_PEER, TIER_DISK)]
    k0, k1, k2 = slow[:3]
    scorer.record([k0], distance=0)              # k0 reused imminently
    store.fetch(k0)
    store.fetch(k1)                              # k1 unpredicted
    store.fetch(k2)                              # overflow: evict one
    assert k0 in store._cache                    # predicted copy survives
    assert k1 not in store._cache                # unpredicted one went
    assert store.stats.cache_evictions_learned == 1
    assert store.stats.cache_evictions_lru == 0
    store.close()


def test_store_without_scorer_counts_no_learned_evictions():
    layers = make_store_layers()
    tc = TierConfig(num_shards=3, shard_dram_experts=2, cache_experts=2)
    store = TieredExpertStore(layers, tc)
    for k in sorted(store.home_shard):
        store.fetch(k)
    assert store.stats.cache_evictions > 0
    assert store.stats.cache_evictions_learned == 0
    assert store.stats.cache_evictions_lru == 0
    store.close()


# ---------------------------------------------------------------------------
# int8 cold tiers

def _roundtrip_bound(a, b):
    """|dequant(quant(b)) - b| <= scale/2 per element, scale from b."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    s = np.max(np.abs(b), axis=0) / 127.0
    assert np.all(np.abs(a - b) <= np.maximum(s, 1e-12) * 0.5 + 1e-6)


def test_int8_roundtrip_bound_and_fetch_bytes():
    layers = make_store_layers()
    ref = HostExpertStore(layers)
    tc = TierConfig(num_shards=3, shard_dram_experts=2, cache_experts=0,
                    cold_dtype="int8")
    store = TieredExpertStore(layers, tc)
    assert store.cold_bytes_per_expert < store.bytes_per_expert
    # int8 payload + f32 scales vs full precision: >= 2x smaller for f32
    assert store.bytes_per_expert / store.cold_bytes_per_expert >= 2.0
    cold_seen = 0
    for key in sorted(store.home_shard):
        w, info = store.fetch(key)
        if info.tier in (TIER_PEER, TIER_DISK):
            cold_seen += 1
            assert info.nbytes == store.cold_bytes_per_expert
            for a, b in zip(w, ref.get(key)):
                _roundtrip_bound(a, b)
        else:
            for a, b in zip(w, ref.get(key)):    # warm tier stays bit-exact
                np.testing.assert_array_equal(a, b)
    assert cold_seen > 0
    assert store.stats.quantized_fetches == cold_seen
    store.close()


def test_cold_dtype_none_is_bit_exact():
    layers = make_store_layers()
    ref = HostExpertStore(layers)
    tc = TierConfig(num_shards=3, shard_dram_experts=2, cache_experts=2)
    store = TieredExpertStore(layers, tc)
    for key in sorted(store.home_shard):
        for a, b in zip(store.fetch(key)[0], ref.get(key)):
            np.testing.assert_array_equal(a, b)
    assert store.stats.quantized_fetches == 0
    store.close()


def test_ledger_invariants_under_cold_demote_path():
    """The store-level interleaving property with int8 cold tiers: the
    ledger stays consistent and every fetch's weights stay within the
    quantization bound of the reference."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    layers = make_store_layers(n_layers=2, e=6)
    ref = HostExpertStore(layers)
    keys = [(li, e) for li in range(2) for e in range(6)]
    ops = st.lists(
        st.tuples(st.sampled_from(["fetch", "demote", "pin", "unpin"]),
                  st.sampled_from(keys)),
        min_size=1, max_size=60)

    @settings(deadline=None, max_examples=30)
    @given(ops=ops)
    def run(ops):
        tc = TierConfig(num_shards=3, shard_dram_experts=2, cache_experts=3,
                        cold_dtype="int8")
        store = TieredExpertStore(layers, tc)
        pins = []
        try:
            for op, k in ops:
                if op == "fetch":
                    w, info = store.fetch(k)
                    assert info.tier in (TIER_HOST, TIER_PEER, TIER_DISK)
                    for a, b in zip(w, ref.get(k)):
                        _roundtrip_bound(a, b)
                elif op == "demote":
                    store.demote(k)
                elif op == "pin":
                    store.pin(k)
                    pins.append(k)
                elif op == "unpin" and k in pins:
                    store.unpin(k)
                    pins.remove(k)
                store.ledger.check(keys)
        finally:
            store.close()

    run()


def test_int8_logit_deviation_pinned(backbone):
    """Quantize->dequantize every routed expert weight in the trained
    backbone and forward the model: the max logit deviation stays small
    (bounded numerics) but nonzero (it IS lossy — which is why
    ``cold_dtype`` is opt-in)."""
    import jax
    import jax.numpy as jnp
    cfg, model, params, _ = backbone

    def qdq(w):
        w = np.asarray(w, np.float32)
        s = np.max(np.abs(w), axis=-2, keepdims=True) / 127.0
        s = np.where(s > 0, s, 1.0)
        q = np.clip(np.rint(w / s), -127, 127)
        return jnp.asarray((q * s).astype(np.float32))

    from jax.tree_util import DictKey, tree_map_with_path

    def maybe_q(path, leaf):
        names = [p.key for p in path if isinstance(p, DictKey)]
        if "moe" in names and names[-1] in ("w_gate", "w_up", "w_down"):
            return qdq(leaf)
        return leaf

    params_q = tree_map_with_path(maybe_q, params)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    lg = np.asarray(model.forward(params, {"tokens": tokens}))
    lq = np.asarray(model.forward(params_q, {"tokens": tokens}))
    dev = float(np.max(np.abs(lg - lq)))
    assert 0 < dev < 0.25, dev

    # the test's vectorised round-trip matches the store's per-expert one
    tc = TierConfig(cold_dtype="int8")
    store = TieredExpertStore(make_store_layers(), tc)
    ws = store.base.get((0, 0))
    deq = store._dequantize(*store._quantize(ws))
    for a, b in zip(deq, ws):
        np.testing.assert_allclose(a, np.asarray(qdq(b)), rtol=0, atol=1e-6)
    store.close()


# ---------------------------------------------------------------------------
# engine integration

@pytest.fixture(scope="module")
def backbone():
    return tiny_backbone()


def _gen(eng, prompts):
    return eng.generate(prompts, max_new=MAX_NEW, cache_len=CACHE_LEN)


def test_learned_replacement_stream_parity_and_win(backbone):
    """learned vs lru at equal capacity: token-identical streams, victim
    provenance counted at both cache levels, and fewer slow-tier fetches
    (the tier-1 cache retains the copies predicted soonest-reused instead
    of cycling them out LRU-style)."""
    cfg, model, params, _ = backbone
    from repro.serving.scheduler import BatchedOffloadEngine
    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    tc = TierConfig(num_shards=4, shard_dram_experts=3,
                    cache_experts=n_total // 2)
    runs = {}
    for pol in ("lru", "learned"):
        eng = BatchedOffloadEngine(model, params,
                                   NextLayerAllPolicy(cfg.moe.num_experts),
                                   capacity=16, eviction=pol, max_batch=4,
                                   tiers=tc)
        outs = _gen(eng, PROMPTS)
        f = eng.stats.fetches_by_tier
        runs[pol] = (outs, f.get(TIER_PEER, 0) + f.get(TIER_DISK, 0), eng)
        eng.core.store.close()
    assert runs["lru"][0] == runs["learned"][0]          # streams identical
    assert runs["learned"][1] < runs["lru"][1]           # fewer slow fetches
    lrn = runs["learned"][2]
    assert lrn.stats.evictions_learned > 0               # tier 0 informed
    assert lrn.core.store.stats.cache_evictions_learned > 0   # tier 1 too
    assert runs["lru"][2].stats.evictions_learned == 0


def test_learned_single_host_stream_parity(backbone):
    """Learned replacement without tiers: the scorer still drives the
    tier-0 slots and streams stay identical to the LRU engine."""
    cfg, model, params, _ = backbone
    from repro.serving.scheduler import BatchedOffloadEngine
    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    outs = {}
    for pol in ("lru", "learned"):
        eng = BatchedOffloadEngine(model, params,
                                   NextLayerAllPolicy(cfg.moe.num_experts),
                                   capacity=max(8, n_total // 3),
                                   eviction=pol, max_batch=4)
        outs[pol] = _gen(eng, PROMPTS)
    assert outs["lru"] == outs["learned"]


def test_horizon_clamp_recovers_thrash_regime(backbone):
    """At admission-minimum tier-0 capacity, deep prefetch used to evict
    the next layer's own working set (PR 5 measured hit 0.57). The clamp
    suppresses deep insertions when they cannot fit, so the horizon-aware
    config now matches the fixed-horizon one instead of losing to it —
    and the clamps are counted."""
    cfg, model, params, _ = backbone
    from repro.serving.scheduler import BatchedOffloadEngine
    min_cap = 4 * cfg.moe.top_k
    res = {}
    for name, hz in (("aware", (1, 1, 2, 3)), ("fixed", (1, 1, 1, 1))):
        tc = TierConfig(num_shards=4, shard_dram_experts=3, cache_experts=8,
                        horizons=hz)
        eng = BatchedOffloadEngine(model, params,
                                   NextLayerAllPolicy(cfg.moe.num_experts),
                                   capacity=min_cap, eviction="lru",
                                   max_batch=4, tiers=tc)
        res[name] = (_gen(eng, PROMPTS), eng.stats.hit_rate,
                     eng.stats.horizon_clamps)
        eng.core.store.close()
    assert res["aware"][0] == res["fixed"][0]            # parity holds
    assert res["aware"][1] >= res["fixed"][1]            # no thrash loss
    assert res["aware"][2] > 0                           # clamp engaged
    assert res["fixed"][2] == 0                          # nothing to clamp


class _ConfidencePolicy(Policy):
    """All-experts prediction with a fixed reported confidence."""
    name = "confidence-stub"
    stateless = True

    def __init__(self, num_experts, conf):
        self.e = num_experts
        self.conf = conf

    def predict(self, t, layer):
        return np.arange(self.e)

    def predict_scored(self, t, layer):
        ids = np.arange(self.e)
        return ids, np.full(self.e, self.conf, np.float64)


def test_deep_confidence_gates_deep_prefetch(backbone):
    """TierConfig.deep_confidence prunes deep prefetch per key: below the
    threshold a slow-tier prediction is NOT submitted early (it still
    goes at distance 0), above it deep prefetch proceeds. Streams never
    change — only the submit timeline."""
    cfg, model, params, _ = backbone
    from repro.serving.engine import OffloadEngine
    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    res = {}
    for name, thresh in (("open", 0.2), ("shut", 0.95), ("off", None)):
        tc = TierConfig(num_shards=4, shard_dram_experts=2, cache_experts=4,
                        horizons=(1, 1, 2, 3), deep_confidence=thresh,
                        peer_latency_s=1e-4, peer_bw=1e12,
                        disk_latency_s=3.4e-4, disk_bw=1e12)
        pol = _ConfidencePolicy(cfg.moe.num_experts, conf=0.5)
        eng = OffloadEngine(model, params, pol, n_total,
                            layer_compute_s=1e-3, tiers=tc)
        streams = [eng.generate(p, MAX_NEW, CACHE_LEN) for p in PROMPTS]
        res[name] = (streams, eng.stats.deep_prefetch_hits)
        eng.core.store.close()
    assert res["open"][0] == res["shut"][0] == res["off"][0]
    assert res["open"][1] > 0                    # conf 0.5 >= 0.2: deep runs
    assert res["shut"][1] == 0                   # conf 0.5 < 0.95: pruned
    assert res["off"][1] == res["open"][1]       # None == static gate only


def test_predict_many_layers_with_scores_matches_scalar():
    """The fused multi-layer scored forward returns the same (ids, conf)
    pairs as per-policy predict_scored."""
    import jax

    from repro.configs.base import PredictorConfig
    from repro.core.policies import OnlineMoEBeyondPolicy, PerRequestPolicy
    from repro.core.predictor import predictor_init

    pc = PredictorConfig(token_emb_dim=16, num_model_layers=3, num_experts=8,
                         layer_emb_dim=8, d_model=16, num_layers=2,
                         num_heads=2, d_ff=32, max_seq=16, top_k=3)
    pp = predictor_init(jax.random.PRNGKey(0), pc)
    prp = PerRequestPolicy(lambda: OnlineMoEBeyondPolicy(pp, pc, width=3))
    rng = np.random.default_rng(1)
    rids, lens = [0, 1, 2], [5, 3, 0]
    for r, n in zip(rids, lens):
        prp.begin_request(r)
        for t in range(n):
            prp._get(r).observe(t, 0, [1],
                                rng.normal(size=16).astype(np.float32))
    layers = [1, 2]
    fused = prp.predict_batch_multi_scored(rids, lens, layers)
    for layer in layers:
        for i, rid in enumerate(rids):
            ids_f, conf_f = fused[layer][i]
            ids_s, conf_s = prp._get(rid).predict_scored(lens[i], layer)
            assert sorted(ids_f.tolist()) == sorted(ids_s.tolist())
            order_f, order_s = np.argsort(ids_f), np.argsort(ids_s)
            np.testing.assert_allclose(np.asarray(conf_f)[order_f],
                                       np.asarray(conf_s)[order_s],
                                       rtol=1e-5, atol=1e-6)


def test_online_predictor_compiles_once_per_bucket():
    """Per-request policies on one predictor share its compiled forward,
    and a growing prompt compiles once per power-of-two length bucket: the
    serving engine makes a policy per request and predicts at every
    prefill chunk."""
    import jax

    from repro.configs.base import PredictorConfig
    from repro.core.policies import (OnlineMoEBeyondPolicy,
                                     _predictor_forward)
    from repro.core.predictor import predictor_init

    pc = PredictorConfig(token_emb_dim=16, num_model_layers=3, num_experts=8,
                         layer_emb_dim=8, d_model=16, num_layers=2,
                         num_heads=2, d_ff=32, max_seq=32, top_k=3)
    pp = predictor_init(jax.random.PRNGKey(0), pc)
    rng = np.random.default_rng(2)
    pols = [OnlineMoEBeyondPolicy(pp, pc) for _ in range(3)]
    fwd = _predictor_forward(pc)
    assert all(p._apply is fwd for p in pols)
    before = fwd._cache_size()
    for p in pols:
        p.begin_prompt()
        for t in range(16):
            p.observe(t, 0, [1], rng.normal(size=16).astype(np.float32))
            if t >= 8:                           # lengths 9..16: bucket 16
                ids, conf = p.predict_scored(t, 1)
                assert len(ids) == pc.top_k and len(conf) == pc.top_k
    assert fwd._cache_size() - before <= 1

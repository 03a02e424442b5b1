"""Prefetch policies evaluated by the cache simulator (paper §3.1/§4.1.3).

Interface: before layer ``l`` of token ``t`` runs, ``predict(t, l)`` names
experts to prefetch; after the layer runs, ``observe(...)`` reveals ground
truth. Policies:

  NoPrefetchPolicy   — reactive LRU/LFU caching only (on-demand fetch)
  NextLayerAllPolicy — DeepSpeed-MoE: eagerly fetch *every* expert [2]
  GlobalFrequencyPolicy — BrainStorm-style workload-popularity counts [4]
  RandomPolicy       — floor baseline
  MoEInfinityPolicy  — rEAM cosine match against a k-means EAMC [1]
  MoEBeyondPolicy    — the paper: learned transformer predictor
  OraclePolicy       — ground truth (upper bound)

:class:`ReuseDistanceScorer` is the bridge from prediction to *eviction*:
it folds the engine's multi-horizon prediction windows into a per-key
predicted-next-use distance on a logical MoE-layer clock, which the
``policy="learned"`` replacement mode of ``core/cache.ExpertCache`` and the
tier-1 cache of ``serving/expertstore.TieredExpertStore`` consult to pick
the victim predicted furthest from reuse.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.eam import EAMC, REAMBuilder, build_ream


class ReuseDistanceScorer:
    """Predicted-next-use distances for learned cache replacement.

    The engine records every multi-horizon prediction window it obtains
    (``record(keys, distance=d)``: these keys are predicted for use ``d``
    MoE layers from now) and ticks a logical clock once per MoE layer
    computed (``tick``). ``distance(key)`` is then the number of MoE layers
    until the key's soonest *still-future* predicted use, or ``None`` when
    no live prediction covers it — stale predictions (their layer already
    ran) expire rather than protect a key forever.

    Shared by both cache layers: tier-0 slot-buffer eviction
    (``ExpertCache(policy="learned")``) and the tier-1 promoted-copy cache
    (``TieredExpertStore``), so one prediction pass drives victim choice
    across the hierarchy.
    """

    #: prune stale entries when the map outgrows this (keys spaces are
    #: n_moe_layers * num_experts, so this is generous)
    PRUNE_AT = 65536

    def __init__(self):
        self.clock = 0                        # MoE layers computed so far
        self._next_use: Dict[Tuple[int, int], int] = {}

    def record(self, keys: Sequence, distance: int) -> None:
        """Keys predicted for use ``distance`` MoE layers from now (0 =
        the next MoE layer). Keeps the soonest live prediction per key."""
        t = self.clock + distance + 1
        for k in keys:
            cur = self._next_use.get(k)
            if cur is None or cur <= self.clock or t < cur:
                self._next_use[k] = t

    def tick(self, n: int = 1) -> None:
        """One (or ``n``) MoE layer(s) of compute completed."""
        self.clock += n
        if len(self._next_use) > self.PRUNE_AT:
            self._next_use = {k: t for k, t in self._next_use.items()
                              if t > self.clock}

    def distance(self, key) -> Optional[int]:
        """MoE layers until the soonest predicted use of ``key``; ``None``
        when no live prediction covers it."""
        t = self._next_use.get(key)
        if t is None or t <= self.clock:
            return None
        return t - self.clock

    def reset(self) -> None:
        self.clock = 0
        self._next_use.clear()


def _sigmoid(logits: np.ndarray) -> np.ndarray:
    """Per-expert confidence — the same sigmoid probability the paper's
    selection rule thresholds (core/metrics.select_experts)."""
    return 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


@functools.lru_cache(maxsize=None)
def _predictor_forward(pcfg):
    """The jitted predictor forward for one ``PredictorConfig``, shared by
    every policy instance built on it: the serving engine makes one policy
    per request, and a jit per instance would compile each shape again for
    every request. The program is named ``predictor_forward`` on the
    device."""
    import jax

    from repro.core.predictor import predictor_apply

    def predictor_forward(pr, e, l, m):
        return predictor_apply(pr, pcfg, e, l, m)

    return jax.jit(predictor_forward)


class Policy:
    name = "base"

    #: True when predict/observe keep no per-request state, so ONE instance
    #: may be shared verbatim across in-flight requests of a batched engine.
    stateless = False

    def begin_prompt(self, trace) -> None:  # noqa: ARG002
        pass

    def observe(self, t: int, layer: int, experts: Sequence[int],
                embedding: Optional[np.ndarray] = None) -> None:
        pass

    def predict(self, t: int, layer: int) -> np.ndarray:
        """Experts to prefetch for (token t, layer)."""
        return np.empty((0,), np.int64)

    def predict_scored(self, t: int, layer: int):
        """(experts, confidences): per-expert confidence in [0, 1] aligned
        with the prediction array, or ``None`` when the policy has no
        confidence notion (heuristics). Confidence gates *deep* prefetch:
        the engine only fetches a slow-tier key several layers early when
        the predictor is confident enough (``TierConfig.deep_confidence``).
        """
        return self.predict(t, layer), None

    # --- batched API (serving/scheduler.py) -------------------------------
    # Defaults loop over the scalar interface; vectorised policies override.

    def predict_batch(self, ts: Sequence[int], layer: int) -> List[np.ndarray]:
        """Per-request prefetch sets for a batch of (token-step, layer)."""
        return [self.predict(t, layer) for t in ts]

    def observe_batch(self, ts: Sequence[int], layer: int,
                      experts_per_req: Sequence[Sequence[int]],
                      embeddings: Optional[Sequence] = None) -> None:
        for i, t in enumerate(ts):
            emb = embeddings[i] if embeddings is not None else None
            self.observe(t, layer, experts_per_req[i], emb)


class NoPrefetchPolicy(Policy):
    name = "lru-on-demand"
    stateless = True


class RandomPolicy(Policy):
    # NOT stateless: predict() advances the shared rng, so per-request
    # streams would depend on batch interleaving if one instance were
    # shared — batched engines should build one per request.
    name = "random"

    def __init__(self, num_experts: int, width: int, seed: int = 0):
        self.e = num_experts
        self.width = width
        self.rng = np.random.default_rng(seed)

    def predict(self, t, layer):
        return self.rng.choice(self.e, size=min(self.width, self.e),
                               replace=False)


class NextLayerAllPolicy(Policy):
    """DeepSpeed-MoE-style: prefetch the whole next layer (over-fetches)."""
    name = "next-layer-all"
    stateless = True

    def __init__(self, num_experts: int):
        self.e = num_experts

    def predict(self, t, layer):
        return np.arange(self.e)


class GlobalFrequencyPolicy(Policy):
    """BrainStorm-style: retain historically popular experts per layer."""
    name = "global-frequency"
    stateless = True

    def __init__(self, train_traces, num_layers: int, num_experts: int,
                 width: int):
        counts = np.zeros((num_layers, num_experts), np.float64)
        for tr in train_traces:
            counts += build_ream(tr, num_layers, num_experts)
        self.top = np.argsort(-counts, axis=1)[:, :width]

    def predict(self, t, layer):
        return self.top[layer]


class OraclePolicy(Policy):
    name = "oracle"

    def begin_prompt(self, trace):
        self.trace = trace

    def predict(self, t, layer):
        return np.unique(self.trace.experts[t, layer])


class MoEInfinityPolicy(Policy):
    """Paper §4.1.4: partial rEAM -> cosine match vs EAMC -> prefetch the
    matched sketch's expert group for the upcoming layer."""
    name = "moe-infinity"

    def __init__(self, train_traces, num_layers: int, num_experts: int,
                 width: int, eamc_capacity: int = 32, seed: int = 0):
        self.num_layers = num_layers
        self.num_experts = num_experts
        self.width = width
        self.eamc = EAMC(num_layers, num_experts, eamc_capacity)
        reams = [build_ream(tr, num_layers, num_experts)
                 for tr in train_traces]
        if reams:
            self.eamc.fit(reams, seed=seed)
        self.partial: REAMBuilder | None = None

    def begin_prompt(self, trace):  # noqa: ARG002
        self.partial = REAMBuilder(self.num_layers, self.num_experts)

    def observe(self, t, layer, experts, embedding=None):
        self.partial.add(layer, experts)

    def predict(self, t, layer):
        return self.eamc.predict_layer(self.partial.counts, layer,
                                       self.width)


class MoEBeyondPolicy(Policy):
    """The paper's learned predictor.

    For simulation speed the per-layer predictions for a whole trace are
    precomputed in one batched, causally-masked call — position t sees only
    tokens <= t, so this is exactly the online one-layer-look-ahead."""
    name = "moe-beyond"

    def __init__(self, predictor_params, pcfg, width: Optional[int] = None):
        self.params = predictor_params
        self.pcfg = pcfg
        self.width = width or pcfg.top_k
        self._apply = _predictor_forward(pcfg)
        self._pred: Dict[int, np.ndarray] = {}

    def begin_prompt(self, trace):
        import jax.numpy as jnp

        from repro.core.metrics import select_experts
        pc = self.pcfg
        t = min(trace.num_tokens, pc.max_seq)
        emb = jnp.asarray(trace.embeddings[None, :t])
        mask = jnp.ones((1, t), bool)
        n_layers = trace.experts.shape[1]
        self._pred = {}
        for layer in range(n_layers):
            lids = jnp.full((1, t), layer, jnp.int32)
            logits = np.asarray(self._apply(self.params, emb, lids, mask))[0]
            logits = logits[:, : pc.num_experts]          # horizon slot 0
            # prefetch uses pure top-k (threshold only matters for the
            # paper's accuracy metric; an empty prefetch set helps nobody)
            sel = select_experts(logits, self.width, threshold=-1e9)
            self._pred[layer] = [np.nonzero(s)[0] for s in sel]
        self._t_max = t

    def predict(self, t, layer):
        if t >= self._t_max or layer not in self._pred:
            return np.empty((0,), np.int64)
        return self._pred[layer][t]


class CrossLayerPolicy(Policy):
    """Beyond-paper (DESIGN.md §3): exploit the cross-layer gate correlation
    MoE-Infinity ignores — predict layer l's experts from the experts that
    JUST fired at layer l-1 for the same token, via conditional frequencies
    P(e_l | e_{l-1}) estimated from training traces. Zero learned weights;
    complements (and composes with) the request-level rEAM signal."""
    name = "cross-layer"

    def __init__(self, train_traces, num_layers: int, num_experts: int,
                 width: int, alpha: float = 0.5):
        self.width = width
        self.e = num_experts
        # cond[l][a, b] = count(expert b fires at layer l | a fired at l-1)
        self.cond = np.full((num_layers, num_experts, num_experts), alpha)
        self.prior = np.full((num_layers, num_experts), alpha)
        for tr in train_traces:
            t_steps, n_layers, _ = tr.experts.shape
            for t in range(t_steps):
                for layer in range(n_layers):
                    cur = np.unique(tr.experts[t, layer])
                    self.prior[layer, cur] += 1
                    if layer > 0:
                        prev = np.unique(tr.experts[t, layer - 1])
                        for a in prev:
                            self.cond[layer, a, cur] += 1
        self._last: Dict[int, np.ndarray] = {}

    def begin_prompt(self, trace=None):  # noqa: ARG002
        self._last = {}

    def observe(self, t, layer, experts, embedding=None):
        self._last[layer] = np.asarray(experts)

    def predict(self, t, layer):
        if layer == 0 or (layer - 1) not in self._last:
            scores = self.prior[layer]
        else:
            prev = self._last[layer - 1]
            scores = self.cond[layer, prev].sum(axis=0)
        return np.argsort(-scores)[: self.width]


class OnlineMoEBeyondPolicy(Policy):
    """Live-serving variant of MoEBeyondPolicy: accumulates the prompt's
    token embeddings as they are observed and predicts incrementally —
    used by serving/engine.py where no trace exists up front."""
    name = "moe-beyond-online"

    def __init__(self, predictor_params, pcfg, width: Optional[int] = None):
        self.params = predictor_params
        self.pcfg = pcfg
        self.width = width or pcfg.top_k
        self._apply = _predictor_forward(pcfg)
        self._emb: list = []
        self._seen_t = -1

    def begin_prompt(self, trace=None):  # noqa: ARG002
        self._emb = []
        self._seen_t = -1

    def observe(self, t, layer, experts, embedding=None):
        if embedding is not None and t > self._seen_t:
            self._emb.append(np.asarray(embedding, np.float32))
            self._seen_t = t

    def predict(self, t, layer):
        return self.predict_scored(t, layer)[0]

    def predict_scored(self, t, layer):  # noqa: ARG002
        # the batched forward with one row: the embeddings observed so far
        # (token t itself is appended by the engine before deeper layers
        # run), padded to the power-of-two length bucket, so a growing
        # prompt compiles once per bucket, not once per length
        return self.predict_many_layers([self], [layer],
                                        with_scores=True)[layer][0]

    @staticmethod
    def batchable(policies: Sequence["Policy"]) -> bool:
        """True when one vectorised forward can serve every instance: all
        OnlineMoEBeyondPolicy sharing the same predictor weights (the
        per-request-factory pattern closes over one trained predictor)."""
        if not policies:
            return False
        first = policies[0]
        return (isinstance(first, OnlineMoEBeyondPolicy) and
                all(isinstance(p, OnlineMoEBeyondPolicy)
                    and p.params is first.params and p.pcfg == first.pcfg
                    for p in policies))

    @staticmethod
    def predict_many(policies: Sequence["OnlineMoEBeyondPolicy"],
                     layer: int) -> List[np.ndarray]:
        """Cross-request batched prediction: ONE jitted predictor forward
        for all in-flight requests instead of a per-request Python loop.

        Requests are right-padded to a shared power-of-two length bucket
        (bounding recompiles); the causal+padding mask makes position
        ``n_i - 1`` of each row attend to exactly that request's observed
        embeddings, so per-request results match the scalar ``predict``.
        """
        return OnlineMoEBeyondPolicy.predict_many_layers(
            policies, [layer])[layer]

    @staticmethod
    def predict_many_layers(policies: Sequence["OnlineMoEBeyondPolicy"],
                            layers: Sequence[int],
                            with_scores: bool = False,
                            ) -> Dict[int, List]:
        """``predict_many`` across a lookahead window of MoE layers: one
        jitted forward serves every (request, future-layer) pair — the
        layer id is a per-row input, so deeper-horizon predictions ride
        the same batch as next-layer ones instead of multiplying predictor
        calls. Returns {layer: per-request prediction arrays}; per-request
        results match the scalar ``predict(t, layer)`` for each layer.
        With ``with_scores`` each per-request entry is an
        ``(experts, confidences)`` pair instead — the sigmoid probability
        of each selected expert, matching ``predict_scored``."""
        import jax.numpy as jnp

        from repro.core.metrics import select_experts
        pc = policies[0].pcfg
        ns = [min(len(p._emb), pc.max_seq) for p in policies]

        def empty():
            ids = np.empty((0,), np.int64)
            return (ids, np.empty((0,), np.float64)) if with_scores else ids

        out: Dict[int, List] = {
            layer: [empty()] * len(policies) for layer in layers}
        live = [i for i, n in enumerate(ns) if n > 0]
        if not live or not layers:
            return out
        rows = [(i, layer) for layer in layers for i in live]
        # power-of-two buckets for both the sequence and the row count
        # bound recompiles; padded rows are fully masked and never read
        tb = _pow2_ceil(max(ns[i] for i in live))
        rb = _pow2_ceil(len(rows))
        emb = np.zeros((rb, tb, pc.token_emb_dim), np.float32)
        mask = np.zeros((rb, tb), bool)
        lids = np.zeros((rb, tb), np.int32)
        for j, (i, layer) in enumerate(rows):
            emb[j, : ns[i]] = np.stack(policies[i]._emb[-ns[i]:])
            mask[j, : ns[i]] = True
            lids[j] = layer
        logits = np.asarray(policies[0]._apply(
            policies[0].params, jnp.asarray(emb), jnp.asarray(lids),
            jnp.asarray(mask)))
        for j, (i, layer) in enumerate(rows):
            lg = logits[j, ns[i] - 1, : pc.num_experts]
            sel = select_experts(lg, policies[i].width, threshold=-1e9)
            ids = np.nonzero(sel)[0]
            out[layer][i] = (ids, _sigmoid(lg[ids])) if with_scores else ids
        return out


class PerRequestPolicy:
    """Per-request policy state behind the batched predict/observe API.

    The batched engine shares ONE ExpertCache across in-flight requests but
    prediction state (rEAM sketches, observed embeddings, precomputed trace
    predictions) is per request. ``factory()`` builds a fresh Policy for
    every admitted request; a stateless policy instance may be passed
    directly and is then shared across all requests.
    """

    def __init__(self, policy_or_factory, force_shared: bool = False):
        """force_shared: accept a *stateful* instance as shared anyway —
        only sound when at most one request is ever in flight (the batch-1
        OffloadEngine)."""
        if isinstance(policy_or_factory, Policy):
            pol = policy_or_factory
            if not (pol.stateless or force_shared):
                raise ValueError(
                    f"policy {pol.name!r} keeps per-request state; pass a "
                    f"factory (e.g. lambda: {type(pol).__name__}(...)) so "
                    "each request gets its own instance")
            self._shared: Optional[Policy] = pol
            self._factory = None
        else:
            self._shared = None
            self._factory = policy_or_factory
        self._per_req: Dict[int, Policy] = {}

    def _get(self, rid: int) -> Policy:
        if self._shared is not None:
            return self._shared
        return self._per_req[rid]

    def begin_request(self, rid: int, trace=None) -> None:
        if self._shared is None:
            self._per_req[rid] = self._factory()
            self._per_req[rid].begin_prompt(trace)
        else:
            self._shared.begin_prompt(trace)

    def end_request(self, rid: int) -> None:
        self._per_req.pop(rid, None)

    def replay_prefix(self, rid: int, experts_by_layer) -> None:
        """Feed a prefix-cache hit's recorded activations into the request's
        policy as observations — the request skips the prefill that would
        have produced them, so replay is how rEAM-style predictors still see
        the prompt's routing signature. ``experts_by_layer`` maps MoE-layer
        ordinal -> expert-id array (no embeddings exist for skipped tokens,
        so embedding-driven policies simply ignore the replay)."""
        pol = self._get(rid)
        for mi in sorted(experts_by_layer):
            pol.observe(0, mi, np.asarray(experts_by_layer[mi]), None)

    def predict_batch(self, rids: Sequence[int], ts: Sequence[int],
                      layer: int) -> List[np.ndarray]:
        if self._shared is not None:   # shared policy: use its batched path
            return self._shared.predict_batch(ts, layer)
        pols = [self._get(r) for r in rids]
        if len(pols) > 1 and OnlineMoEBeyondPolicy.batchable(pols):
            # one jitted predictor forward across in-flight requests
            return OnlineMoEBeyondPolicy.predict_many(pols, layer)
        return [p.predict(t, layer) for p, t in zip(pols, ts)]

    def predict_batch_multi(self, rids: Sequence[int], ts: Sequence[int],
                            layers: Sequence[int],
                            ) -> Dict[int, List[np.ndarray]]:
        """Per-request prefetch sets for a *lookahead window* of MoE
        layers — the horizon-aware engine asks for layers ``mi .. mi+H-1``
        at once and gates each predicted key on its tier's required
        lookahead depth. Online-predictor policies fuse the whole window
        into one forward; everything else loops ``predict_batch``."""
        pols = [self._get(r) for r in rids]
        if (self._shared is None and len(layers) > 0
                and OnlineMoEBeyondPolicy.batchable(pols)):
            return OnlineMoEBeyondPolicy.predict_many_layers(pols, layers)
        return {layer: self.predict_batch(rids, ts, layer)
                for layer in layers}

    def predict_batch_multi_scored(self, rids: Sequence[int],
                                   ts: Sequence[int],
                                   layers: Sequence[int],
                                   ) -> Dict[int, List[tuple]]:
        """``predict_batch_multi`` with confidences: each per-request entry
        is an ``(experts, confidences)`` pair (confidences ``None`` for
        policies without a confidence notion). The engine's per-horizon
        confidence gate and the ReuseDistanceScorer both consume this —
        one fused forward still serves the whole window for the online
        predictor."""
        pols = [self._get(r) for r in rids]
        if (self._shared is None and len(layers) > 0
                and OnlineMoEBeyondPolicy.batchable(pols)):
            return OnlineMoEBeyondPolicy.predict_many_layers(
                pols, layers, with_scores=True)
        return {layer: [p.predict_scored(t, layer)
                        for p, t in zip(pols, ts)]
                for layer in layers}

    def observe_batch(self, rids: Sequence[int], ts: Sequence[int],
                      layer: int, experts_per_req, embeddings=None) -> None:
        if self._shared is not None:
            self._shared.observe_batch(ts, layer, experts_per_req,
                                       embeddings)
            return
        for i, (r, t) in enumerate(zip(rids, ts)):
            emb = embeddings[i] if embeddings is not None else None
            self._get(r).observe(t, layer, experts_per_req[i], emb)

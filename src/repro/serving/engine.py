"""Offloaded serving engines — the paper's deployment scenario as a real
decode loop, not just a trace simulator.

The decode step is executed layer-by-layer: attention halves are jitted,
*batched* device programs (per-request KV-cache rows gathered/scattered
around a vmapped single-row core, compiled once per padding bucket); the
policy's prediction for MoE layer i+1 is submitted to the host->device
channel before layer i's attention runs, so prefetch transfers overlap
compute (offload.OverlapTracker charges only the un-overlapped remainder
as stall). At each MoE layer the router reveals the truth, misses are
demand-fetched, every expert needed by any in-flight request is *pinned*
in the ExpertCache for the duration of the expert compute, and the expert
FFN reads each distinct slot of the tier-0 slot buffer once, in place
(kernels/expert_ffn.py).

``OffloadEngine`` keeps the original batch-1 API on top of the shared
``DecodeCore``; ``serving/scheduler.py`` builds the multi-request
continuous-batching engine on the same core. With capacity == all experts
both are bit-identical to the monolithic ``model.decode_step`` — tests
assert this.

The core speaks two KV layouts: contiguous per-request rows (the batch-1
fallback and ring-buffer kinds), and the **block-paged** layout of
serving/kvpool.py — ``step(..., tables=)`` scatters K/V through per-request
block tables, and ``prefill_chunk`` absorbs a prompt chunk of one request
through the same paged pools (power-of-two chunk buckets, per-token math
identical to decode, so streams stay token-identical). The paged *read*
path compiles to the paged flash-decode kernel
(kernels/paged_attention.py) selected by ``use_kernel``/``kernel_backend``;
``use_kernel=False`` keeps the PR-2 gather-and-materialise route as the
parity reference.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import LatencyStats, f1_over_window
from repro.core.policies import PerRequestPolicy, Policy
from repro.core.tracing import moe_layer_ids
from repro.kernels import ops
from repro.kernels.expert_ffn import pack_slots, unpack_slots
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.models.common import ffn_apply, rms_norm
from repro.serving.offload import (CHANNEL_SHIP, TIER_HOST, TIER_PEER,
                                   HostExpertStore, OverlapTracker,
                                   make_offload_cache)
from repro.serving.telemetry import (NULL_TELEMETRY, PID_ENGINE,
                                     PID_REQUESTS)


# the routed-expert stacks of a MoE layer's "moe" dict: they live in the
# host expert store, and on the device only in tier-0 slots
ROUTED = ("w_gate", "w_up", "w_down")

# one compiled initializer per layer signature: a MoE layer's expert stacks
# are drawn by one device program and leave the device right after
_block_init = jax.jit(T.block_init, static_argnums=(1, 2, 3, 4))


def init_offload_params(cfg, key):
    """``transformer.lm_init(key, cfg)`` for the offload engine: the same
    key schedule and tree, but every MoE layer's routed-expert stacks are
    drawn on the device one layer at a time, copied to the host and freed
    on the device. Scanned layers' stacks are filled into one preallocated
    host array per pattern position, so neither side ever holds more than
    one layer's experts beyond the host store itself. Everything else is
    on the device."""
    return T.lm_init(key, cfg, stack_fn=_offload_stack_init)


def _offload_stack_init(key, cfg, dtype):
    """``transformer.stack_init``'s layout and keys, routed stacks on the
    host (see ``init_offload_params``)."""
    kinds = cfg.layer_kinds()
    n_head, n_groups, n_tail = T._layer_split(cfg)
    pat = len(cfg.block_pattern)
    keys = jax.random.split(key, cfg.num_layers)

    def block(li, host=None, g=0):
        p = _block_init(keys[li], cfg, kinds[li], T._layer_is_moe(cfg, li),
                        dtype)
        if "moe" not in p:
            return p
        moe = dict(p["moe"])
        for name in ROUTED:
            w = np.asarray(moe.pop(name))          # device -> host
            if host is None:
                moe[name] = w
            else:
                if name not in host:
                    host[name] = np.empty((n_groups,) + w.shape, w.dtype)
                host[name][g] = w
        return dict(p, moe=moe)

    head = [block(i) for i in range(n_head)]
    scan = []
    for j in range(pat):
        host: Dict[str, np.ndarray] = {}
        per_group = [block(n_head + g * pat + j, host, g)
                     for g in range(n_groups)]
        st = (jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
              if n_groups else {})
        if host:
            st["moe"].update(host)
        scan.append(st)
    tail_base = n_head + n_groups * pat
    tail = [block(tail_base + i) for i in range(n_tail)]
    return {"head": head, "scan": tuple(scan), "tail": tail}


def unstack_layers(cfg, params) -> List[dict]:
    """Per-layer params list from the scan-stacked pytree."""
    st = params["stack"]
    n_head, n_groups, n_tail = T._layer_split(cfg)
    pat = len(cfg.block_pattern)
    layers = list(st["head"])
    for g in range(n_groups):
        for j in range(pat):
            layers.append(jax.tree.map(lambda x, g=g: x[g], st["scan"][j]))
    layers.extend(st["tail"])
    return layers


def _without_routed(lp: dict) -> dict:
    """A layer's params minus its routed-expert stacks."""
    if "moe" not in lp:
        return lp
    return dict(lp, moe={k: v for k, v in lp["moe"].items()
                         if k not in ROUTED})


def sample_token(logits: np.ndarray, temperature: float,
                 rng: np.random.Generator) -> int:
    """Greedy/temperature sampling shared by the batch-1 and batched
    engines — parity between their token streams depends on this being
    one implementation."""
    if temperature <= 0:
        return int(np.argmax(logits))
    p = np.exp((logits - logits.max()) / temperature)
    return int(rng.choice(len(p), p=p / p.sum()))


def bucket_size(n: int, max_batch: int) -> int:
    """Smallest power of two >= n (capped at max_batch) — the padding
    buckets the jitted halves are compiled for."""
    b = 1
    while b < n:
        b *= 2
    return min(b, max(max_batch, n))


@dataclass
class EngineStats:
    """Counters every engine accumulates across runs (the latency summary
    is replaced per run).

    Token & cache traffic:
      * ``tokens`` — all token positions processed (decode + prefill).
      * ``hits`` / ``misses`` — ExpertCache residency at access time; an
        expert needed by several lanes in one step counts once per lane.
      * ``fetch_bytes`` — bytes moved host->device into expert slots over
        the engine's lifetime (coalesced re-fetches of an in-flight
        transfer are NOT re-counted — see ``fetches_deduped``).

    Modeled fetch timeline (seconds of the OverlapTracker's clock):
      * ``sim_stall_s`` — overlap-aware modeled stall: only the part of
        each transfer NOT hidden behind credited compute.
      * ``blocking_stall_s`` — the every-fetch-stalls model (upper bound);
        with zero credited compute ``sim_stall_s`` degenerates to it.
      * ``overlapped_s`` — transfer seconds hidden behind compute.

    Step & prefill accounting:
      * ``steps`` — batched decode steps executed.
      * ``prefill_tokens`` — prompt tokens absorbed by chunked prefill.
      * ``prefill_chunks`` — chunked-prefill programs executed.
      * ``fallback_prefill_tokens`` — prompt tokens that had to stream
        token-by-token through decode because the stack can't
        chunk-prefill (ring/recurrent kinds) or paging is off; excludes
        each prompt's final token (decode runs it on every path to
        produce the first sampled logits).

    Admission & scheduling:
      * ``rejected_requests`` — requests refused at admission because
        their worst case exceeds the whole pool (they retire immediately
        with an empty result instead of aborting the run).
      * ``preemptions`` — evict-and-resume events: a running request's KV
        blocks were released (after publishing to the prefix index) to
        make room for a more urgent waiter; it re-admits later with its
        stream intact.

    Tier breakdowns (tiered expert store; single-host engines report
    everything under tier 1; keys are storage tiers: 1 = local host DRAM,
    2 = peer-host shard over the interconnect, 3 = disk/mmap, 4 = the
    compute-dispatch *ship* channel — token round trips to peer-resident
    experts, so "waiting on remote compute" is attributed separately from
    "waiting on weights"):
      * ``stall_by_tier`` — un-overlapped modeled stall seconds attributed
        to the tier whose transfer finished last (the critical path).
      * ``overlapped_by_tier`` — hidden transfer seconds per tier.
      * ``fetches_by_tier`` / ``fetch_bytes_by_tier`` — fetch counts and
        bytes served per source tier.
      * ``deep_prefetch_hits`` — expert uses served by an entry prefetched
        more than one MoE layer ahead (horizon-aware deep prefetch of
        slow-tier experts).
      * ``fetches_deduped`` — re-fetches coalesced onto a transfer already
        in flight on the same tier channel (the slot was released before
        the modeled transfer completed, then the key was demanded again):
        no second transfer is queued and no bytes are re-charged.

    Compute dispatch (``TierConfig.dispatch`` = ``"ship"``/``"auto"``;
    zero in fetch-only engines):
      * ``ships`` — expert groups computed remotely: the token batch was
        shipped to the peer shard holding the expert instead of the
        expert's weights being fetched (no tier-0 insert, no cache churn).
      * ``ship_bytes`` — activation bytes shipped over the interconnect
        (tokens out + FFN outputs back; compare ``fetch_bytes``).
      * ``ship_tokens`` — tokens computed remotely across all ships.

    Learned replacement & horizon control:
      * ``evictions_learned`` / ``evictions_lru`` — with
        ``replacement="learned"``, tier-0 slot evictions whose victim
        choice was prediction-informed vs the pure-LRU fallback (mirrors
        :class:`~repro.core.cache.CacheStats`; the store's tier-1 cache
        keeps its own split in StoreStats).
      * ``horizon_clamps`` — deep-prefetch submissions cut short because a
        distance's new keys would not fit the tier-0 slots left over after
        the distance-0 working set and the in-flight pins — the
        anti-thrash guard for admission-minimum capacity.

    Per-run latency:
      * ``latency`` — the latest run's :class:`~repro.core.metrics
        .LatencyStats` (TTFT/per-token percentiles, preemption counts,
        goodput under SLO), or None before any run completes.
    """
    tokens: int = 0
    hits: int = 0
    misses: int = 0
    fetch_bytes: int = 0
    sim_stall_s: float = 0.0
    blocking_stall_s: float = 0.0
    overlapped_s: float = 0.0
    steps: int = 0
    prefill_tokens: int = 0
    prefill_chunks: int = 0
    fallback_prefill_tokens: int = 0
    rejected_requests: int = 0
    preemptions: int = 0
    stall_by_tier: Dict[int, float] = field(default_factory=dict)
    overlapped_by_tier: Dict[int, float] = field(default_factory=dict)
    fetches_by_tier: Dict[int, int] = field(default_factory=dict)
    fetch_bytes_by_tier: Dict[int, int] = field(default_factory=dict)
    deep_prefetch_hits: int = 0
    fetches_deduped: int = 0
    evictions_learned: int = 0
    evictions_lru: int = 0
    horizon_clamps: int = 0
    ships: int = 0
    ship_bytes: int = 0
    ship_tokens: int = 0
    latency: Optional[LatencyStats] = None

    @property
    def hit_rate(self):
        return self.hits / max(self.hits + self.misses, 1)

    @property
    def mean_batch(self):
        """Mean decode lanes per decode step (prefill excluded)."""
        return (self.tokens - self.prefill_tokens) / max(self.steps, 1)

    def as_dict(self) -> dict:
        """Every field as a JSON-ready dict (``latency`` nested or None);
        the blanket serialization the stats-registration lint pins."""
        from dataclasses import asdict
        return asdict(self)


class DecodeCore:
    """Shared batched decode machinery: jitted layer halves, the expert
    cache/slot-buffer control plane, and the per-step host driver.

    KV caches carry ``max_batch + 1`` rows; row ``max_batch`` is a scratch
    row that padding lanes read/write so every bucket's scatter is
    deterministic. Engines own request bookkeeping; the core owns device
    state transforms and stall/hit accounting.
    """

    def __init__(self, model, params, capacity: int, eviction: str = "lru",
                 host_bw: float = 100e9,
                 expert_backend: Optional[str] = None,
                 max_batch: int = 1,
                 layer_compute_s: Union[float, str] = 0.0,
                 max_prefill_chunk: int = 8,
                 kernel: Optional[str] = "auto", tiers=None,
                 telemetry=None):
        cfg = model.cfg
        assert cfg.moe is not None, "offload engine needs an MoE backbone"
        self.cfg = cfg
        self.model = model
        layers = unstack_layers(cfg, params)
        self.kinds = cfg.layer_kinds()
        self.moe_layers = moe_layer_ids(cfg)
        self.moe_index = {li: i for i, li in enumerate(self.moe_layers)}
        from repro.kernels.runtime import default_kernel_backend
        # expert FFN: the grouped Pallas kernel on TPU, its jnp twin
        # elsewhere; an explicit backend string wins
        self.expert_backend = (default_kernel_backend()
                               if expert_backend is None else expert_backend)
        self.max_batch = max_batch
        self.scratch_row = max_batch
        self.max_prefill_chunk = max_prefill_chunk
        # paged attention read path: a kernel backend string threaded into
        # the jitted paged programs, None for the gather parity route, or
        # "auto" for the backend-appropriate default (ServeConfig holds the
        # same rule at the scheduler level and passes the resolved value)
        self.kernel = default_kernel_backend() if kernel == "auto" else kernel
        # telemetry: a pure observer every subsystem below shares. The
        # default is the module-wide no-op singleton, so un-instrumented
        # engines pay one attribute read per guarded site — and the
        # scoreboard capture (_submit_prefetch/_moe_units) is skipped
        # entirely, keeping streams and stats bit-identical either way.
        self.tel = telemetry if telemetry is not None else NULL_TELEMETRY
        self.tel.ensure_track(PID_ENGINE, 1, "decode driver")
        # raw (pre-gating, pre-clamp) distance-0 predicted key sets per
        # MoE ordinal, consumed by _moe_units for the predictor scoreboard
        self._pred_d0: Dict[int, set] = {}

        # host store gets the routed-expert weights (host arrays, whatever
        # side ``params`` held them on); everything else is in self.layers
        # on the device, which holds routed experts only in tier-0 slots.
        # ``tiers`` (a TierConfig) swaps the single-host store for the
        # device/host/peer/disk hierarchy.
        store_layers = [{k: np.asarray(layers[li]["moe"][k]) for k in ROUTED}
                        for li in self.moe_layers]
        self.layers = [jax.device_put(_without_routed(lp)) for lp in layers]
        del layers
        self._tok_emb = jax.device_put(params["tok_emb"])
        # the unembedding program's whole input: the final norm and the
        # output matrix (the tied embedding or the separate head)
        self._head_params = jax.device_put(
            {k: params[k] for k in ("final_ln", "head" if "head" in params
                                    else "tok_emb")})
        # replacement="learned": one ReuseDistanceScorer shared by the
        # tier-0 slot cache and the store's tier-1 cache. _submit_prefetch
        # feeds it the raw (pre-gating) multi-horizon predictions and
        # _moe_units ticks its clock once per MoE layer computed.
        if eviction == "learned":
            from repro.core.policies import ReuseDistanceScorer
            self.scorer = ReuseDistanceScorer()
        else:
            self.scorer = None
        self._conf_threshold = (tiers.deep_confidence
                                if tiers is not None else None)
        if tiers is not None:
            from repro.serving.expertstore import TieredExpertStore
            self.store = TieredExpertStore(store_layers, tiers,
                                           scorer=self.scorer,
                                           telemetry=self.tel)
        else:
            self.store = HostExpertStore(store_layers)
        # compute dispatch (TierConfig.dispatch = "ship"/"auto"): price
        # fetch-vs-ship per (expert, token-count) off the same roofline
        # constants the compute clock uses. weight_bytes is the WIRE size
        # of a peer fetch — the quantized cold size under int8 cold tiers,
        # where a ship runs against the dequantized peer copy instead.
        self.planner = None
        if tiers is not None and tiers.dispatch != "fetch":
            from repro.launch.dryrun import expert_ffn_roofline
            from repro.serving.expertstore import DispatchPlanner
            per_tok_s, base_s = expert_ffn_roofline(cfg)
            wire_w = (self.store.cold_bytes_per_expert
                      if tiers.cold_dtype is not None
                      else self.store.bytes_per_expert)
            self.planner = DispatchPlanner(
                weight_bytes=wire_w,
                act_bytes_per_token=2 * cfg.d_model
                * jnp.dtype(cfg.dtype).itemsize,
                ffn_s_per_token=per_tok_s, ffn_s_base=base_s,
                peer_latency_s=tiers.peer_latency_s,
                peer_bw=tiers.peer_bw, mode=tiers.dispatch)
        # how many MoE layers ahead predictions are asked for: the store's
        # deepest tier decides (single host -> 1, the original behaviour)
        self.max_horizon = self.store.max_horizon
        self.tracker = OverlapTracker(host_bw, telemetry=self.tel)
        # a step's units can route to at most units*top_k distinct experts,
        # which bounds how many ephemeral ship rows one program may stage
        ship_slots = (max(max_batch, max_prefill_chunk) * cfg.moe.top_k
                      if self.planner is not None else 0)
        self.cache, self.slots = make_offload_cache(
            self.store, capacity, eviction, host_bw, tracker=self.tracker,
            scorer=self.scorer, ship_slots=ship_slots,
            telemetry=self.tel)
        self.stats = EngineStats()
        self._init_layer_compute(layer_compute_s)
        self._tok_emb_np = np.asarray(params["tok_emb"], np.float32)
        self._build_fns()

    # ------------------------------------------------------------------
    def _init_layer_compute(self, layer_compute_s: Union[float, str]):
        """The OverlapTracker's compute clock per layer half.

        Every layer advances its attention half after the attention
        program and its FFN half after the dense/expert FFN. A float is
        the legacy uniform knob. ``"roofline"`` derives
        per-layer ``(attn_s, ffn_s)`` from the dry-run's analytic roofline
        (launch/dryrun.decode_layer_roofline) so stall/overlap reports are
        calibrated to the architecture instead of a guess. ``"measured"``
        starts from the roofline shape and rescales it by an EWMA of each
        decode step's real wall clock over its modeled total, so the
        modeled clock tracks this machine's actual speed."""
        self.layer_compute_s = layer_compute_s
        self._calib = 1.0
        self._measure = False
        if isinstance(layer_compute_s, str):
            if layer_compute_s not in ("roofline", "measured"):
                raise ValueError(
                    f"layer_compute_s must be a float, 'roofline' or "
                    f"'measured', got {layer_compute_s!r}")
            from repro.launch.dryrun import decode_layer_roofline
            self._layer_s = decode_layer_roofline(self.cfg,
                                                  batch=self.max_batch)
            self._measure = layer_compute_s == "measured"
        else:
            self._layer_s = [(layer_compute_s, layer_compute_s)
                             ] * self.cfg.num_layers
        self._step_advanced = 0.0

    def _advance(self, li: int, half: int) -> None:
        dt = self._layer_s[li][half] * self._calib
        self.tracker.advance(dt)
        self._step_advanced += dt

    def _calibrate(self, wall_s: float) -> None:
        """Measured-walltime override: rescale the roofline terms so one
        step's modeled compute tracks the real wall clock (EWMA)."""
        if not self._measure or self._step_advanced <= 0:
            return
        target = self._calib * wall_s / self._step_advanced
        self._calib = 0.7 * self._calib + 0.3 * target

    # ------------------------------------------------------------------
    def _build_fns(self):
        cfg = self.cfg
        # bound as a local so no jitted closure reads mutable engine state
        # (tracer-purity): the compiled programs are rebuilt with the core,
        # never silently stale against a reassigned attribute
        expert_backend = self.expert_backend

        @jax.jit
        def embed_fn(tok_emb, tokens):
            # tokens: (N,) -> (N, 1, D)
            return jnp.take(tok_emb, tokens, axis=0)[:, None, :]

        @jax.jit
        def embed_seq_fn(tok_emb, tokens):
            # tokens: (C,) -> (1, C, D), one request's prompt chunk
            return jnp.take(tok_emb, tokens, axis=0)[None, :, :]

        def attn_row(lp, x_row, cache_row, pos, *, kind):
            # one request: x_row (D,), unbatched cache row, scalar pos
            x = x_row[None, None, :]
            cache = jax.tree.map(lambda c: c[None], cache_row)
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            positions = jnp.full((1, 1), pos, jnp.int32)
            if kind == "mla":
                o, nc = mla_mod.mla_apply(lp["attn"], cfg, h, positions,
                                          "decode", cache, pos)
            else:
                o, nc = attn_mod.attn_apply(lp["attn"], cfg, kind, h,
                                            positions, "decode", cache, pos)
            return (x + o)[0, 0], jax.tree.map(lambda c: c[0], nc)

        @partial(jax.jit, static_argnames=("kind",))
        def attn_batched(lp, x, caches, rows, pos, kind):
            # x: (N,1,D); caches: full (max_batch+1, ...); rows/pos: (N,)
            sub = jax.tree.map(lambda c: jnp.take(c, rows, axis=0), caches)
            y, nsub = jax.vmap(partial(attn_row, kind=kind),
                               in_axes=(None, 0, 0, 0))(lp, x[:, 0, :],
                                                        sub, pos)
            new = jax.tree.map(lambda c, n: c.at[rows].set(n), caches, nsub)
            return y[:, None, :], new

        @partial(jax.jit, static_argnames=("kind", "kernel"))
        def paged_attn_step(lp, x, cache, tables, pos, kind, kernel):
            # x: (N,1,D); cache: block pool; tables: (N,W); pos: (N,)
            return T.block_paged_decode(lp, cfg, kind, x, cache, tables,
                                        pos, kernel=kernel)

        @partial(jax.jit, static_argnames=("kind", "kernel"))
        def paged_prefill_step(lp, x, cache, table, t0, n_valid, kind,
                               kernel):
            # x: (1,C,D) chunk of ONE request; table: (W,); t0/n_valid scalar
            return T.block_paged_prefill(lp, cfg, kind, x, cache, table, t0,
                                         n_valid, kernel=kernel)

        @partial(jax.jit, static_argnames=("kind",))
        def paged_copy_fn(cache, src, dst, kind):
            # one pool page src -> dst (copy-on-write for shared blocks)
            return T.block_paged_copy(cfg, kind, cache, src, dst)

        @jax.jit
        def dense_ffn_half(lp, x):
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            return x + ffn_apply(lp["ffn"], h, cfg.ffn_kind)

        @jax.jit
        def router_fn(ln2, w_router, x):
            h = rms_norm(x, ln2, cfg.norm_eps)
            w, idx, _ = moe_mod.route({"w_router": w_router}, cfg, h)
            return h, w, idx

        @jax.jit
        def expert_from_slots(x_norm, weights, slots, wg_buf, wu_buf,
                              wd_buf, shared, x):
            # x_norm/x: (N,1,D); weights: (N,1,k); slots: pack_slots's
            # (2*N*k,) distinct slot ids and per-pair positions
            n, _, k = weights.shape
            ids, pos = unpack_slots(slots, n, k)
            y = ops.expert_ffn(x_norm[:, 0, :], weights[:, 0, :], ids, pos,
                               wg_buf, wu_buf, wd_buf,
                               backend=expert_backend)
            out = x + y[:, None, :]
            if shared is not None:
                out = out + ffn_apply(shared, x_norm, "swiglu")
            return out

        @jax.jit
        def unembed_fn(head_params, x):
            return T.unembed(head_params, cfg, x)

        self._embed = embed_fn
        self._embed_seq = embed_seq_fn
        self._attn = attn_batched
        self._paged_attn = paged_attn_step
        self._paged_prefill = paged_prefill_step
        self._paged_copy = paged_copy_fn
        self._dense_ffn = dense_ffn_half
        self._router = router_fn
        self._expert = expert_from_slots
        self._unembed = unembed_fn

    # ------------------------------------------------------------------
    def alloc_caches(self, cache_len: int) -> List[dict]:
        """Per-layer list of batched (max_batch+1 rows) decode caches."""
        caches = T.stack_cache_init(self.cfg, self.max_batch + 1, cache_len,
                                    jnp.dtype(self.cfg.dtype))
        return unstack_layers(
            self.cfg, {"stack": {"head": caches["head"],
                                 "scan": caches["scan"],
                                 "tail": caches["tail"]}})

    def alloc_paged_caches(self, num_blocks: int,
                           block_size: int) -> List[dict]:
        """Per-layer paged decode caches: layers whose KV grows with the
        sequence get (num_blocks, block_size, ...) pools sharing ONE block-id
        space (serving/kvpool.py); bounded kinds keep max_batch+1 rows."""
        return [T.block_paged_cache_init(self.cfg, self.kinds[li], num_blocks,
                                         block_size, self.max_batch + 1,
                                         jnp.dtype(self.cfg.dtype))
                for li in range(self.cfg.num_layers)]

    @property
    def paged_ok(self) -> bool:
        """Every layer kind is decodable by the paged step (paged pools for
        growing KV, bounded rows for ring buffers)."""
        return all(k in T.PAGED_KINDS + ("local", "chunked")
                   for k in self.kinds)

    @property
    def chunk_prefill_ok(self) -> bool:
        """Chunked prefill needs every layer's state reachable through block
        tables — ring/recurrent kinds fall back to token-by-token prompts."""
        return all(k in T.PAGED_KINDS for k in self.kinds)

    def copy_block(self, caches, src: int, dst: int):
        """Copy pool page ``src -> dst`` in every paged layer — the device
        half of copy-on-write. The scheduler calls this right after
        ``BlockTable.make_private`` swaps a shared block for a private one,
        so the private block starts as a bit-identical copy."""
        src_j = jnp.asarray(src, jnp.int32)
        dst_j = jnp.asarray(dst, jnp.int32)
        for li in range(self.cfg.num_layers):
            if self.kinds[li] in T.PAGED_KINDS:
                caches[li] = self._paged_copy(caches[li], src_j, dst_j,
                                              kind=self.kinds[li])
        return caches

    def paged_block_bytes(self, caches) -> int:
        """Device bytes ONE pool block occupies summed across paged layers —
        the unit the memory high-water scales in."""
        total = 0
        for li, c in enumerate(caches):
            if self.kinds[li] in T.PAGED_KINDS:
                total += sum(v.nbytes // v.shape[0] for v in c.values())
        return total

    def _moe_window(self, li: int) -> List[int]:
        """MoE ordinals of the next ``max_horizon`` MoE layers at/after
        layer ``li`` — the lookahead window horizon-aware prefetch fills."""
        out = []
        for lj in self.moe_layers:
            if lj >= li:
                out.append(self.moe_index[lj])
                if len(out) == self.max_horizon:
                    break
        return out

    def _submit_prefetch(self, policy, rids, ts, li_from: int):
        """Submit predicted experts for the lookahead window starting at
        layer ``li_from``. Distance-0 predictions (the next MoE layer) are
        always prefetched — the original single-layer double-buffer. At
        distance d > 0 a predicted key is prefetched only when

        * the tier it currently resides in needs that much lead time
          (``store.prefetch_horizon(key) > d``): a tier-3 expert is
          requested layers earlier than a tier-1 one, whose prediction can
          wait for the more accurate next-layer pass;
        * its prediction clears ``TierConfig.deep_confidence`` (when set
          and the policy reports confidences): deep lead time is spent
          only on keys the predictor is sure about, pruning wasted
          slow-tier fetches;
        * the keys *fit*: once a distance's not-yet-resident keys exceed
          the tier-0 slots left over after the distance-0 working set and
          the in-flight pins, that distance and everything deeper is
          dropped and ``horizon_clamps`` counts it — the anti-thrash
          guard that stops deep prefetch from churning the next layer's
          own working set at admission-minimum capacity.

        With learned replacement the raw (pre-gating) predictions also
        feed the ReuseDistanceScorer: every predicted (key, distance)
        doubles as a predicted-next-use estimate for eviction.

        With compute dispatch active ("ship"/"auto") a predicted
        peer-resident key the planner prices cheaper to *ship* (estimated
        token count = how many prediction rows name it) is not prefetched
        at any distance: pulling its weights would be exactly the cache
        thrash the ship path exists to avoid. The demand-time decision in
        ``_moe_units`` stays authoritative — if the router sends more
        tokens than predicted, the planner re-prices and may fetch."""
        if policy is None:
            return
        mis = self._moe_window(li_from)
        if not mis:
            return
        scored = (self.scorer is not None
                  or self._conf_threshold is not None)
        with self.tel.span(PID_ENGINE, 1, "predictor.predict"):
            if scored:
                preds = policy.predict_batch_multi_scored(rids, ts, mis)
            elif len(mis) == 1:
                preds = {mis[0]: policy.predict_batch(rids, ts, mis[0])}
            else:
                preds = policy.predict_batch_multi(rids, ts, mis)
        # pass 1: record the WHOLE window into the scorer and decide what
        # fits, before any insertion — the d0 prefetch's evictions must see
        # the deeper layers' predicted distances, not last cycle's stale
        # ones. Records are information and are never clamped; only the
        # insertions are.
        plan = []
        deep_budget, clamped = 0, False
        tel_on = self.tel.enabled
        raw0: set = set()
        for d, mi in enumerate(mis):
            rows = []
            if self.planner is not None:
                mult: Dict = {}
                for pred in preds[mi]:
                    for e in (pred[0] if scored else pred):
                        k = (mi, int(e))
                        mult[k] = mult.get(k, 0) + 1
            for pred in preds[mi]:
                conf = None
                if scored:
                    pred, conf = pred
                keys = [(mi, int(e)) for e in pred]
                if tel_on and d == 0:
                    # scoreboard capture: the RAW next-layer prediction,
                    # before the planner/horizon/fit filters prune what
                    # actually gets prefetched — predictor quality is
                    # about what the model said, not what fit
                    raw0.update(keys)
                if self.scorer is not None and keys:
                    self.scorer.record(keys, distance=d)
                if self.planner is not None:
                    keep = [i for i, k in enumerate(keys)
                            if k in self.cache
                            or self.store.tier_of(k) != TIER_PEER
                            or self.planner.choose(mult[k]) != "ship"]
                    keys = [keys[i] for i in keep]
                    if conf is not None:
                        conf = [conf[i] for i in keep]
                if d > 0:
                    kept = []
                    for i, k in enumerate(keys):
                        if self.store.prefetch_horizon(k) <= d:
                            continue
                        if (self._conf_threshold is not None
                                and conf is not None
                                and conf[i] < self._conf_threshold):
                            continue
                        kept.append(k)
                    keys = kept
                rows.append(keys)
            if d == 0:
                d0 = {k for keys in rows for k in keys}
                deep_budget = max(0, self.cache.capacity - len(d0)
                                  - len(self.cache._pins))
                plan.append((d, rows))
            elif not clamped:
                new = {k for keys in rows for k in keys
                       if k not in self.cache}
                if len(new) > deep_budget:
                    self.stats.horizon_clamps += 1
                    clamped = True      # this distance and deeper dropped
                else:
                    deep_budget -= len(new)
                    plan.append((d, rows))
        # pass 2: submit what fits
        for d, rows in plan:
            for keys in rows:
                if keys:
                    self.cache.prefetch(keys, horizon=d)
        if tel_on:
            self._pred_d0[mis[0]] = raw0
            submitted = sum(len(keys) for _, rows in plan for keys in rows)
            self.tel.instant(PID_ENGINE, 1, "prefetch",
                             {"li_from": li_from, "window": len(mis),
                              "submitted": submitted, "clamped": clamped,
                              "confidence_gated":
                                  self._conf_threshold is not None})

    # ------------------------------------------------------------------
    def _moe_units(self, mi: int, lp, h, w, x, idx_np: np.ndarray,
                   n_real: int):
        """Expert half shared by decode steps and prefill chunks.

        A "unit" is one token needing top-k experts: decode lanes, or the
        tokens of one prefill chunk. h/w/x: (U,1,...) device arrays (pad
        units included); idx_np: (U,k); only the first n_real units touch
        the cache. Returns (x_out, per-live-unit ground-truth sets).

        Compute dispatch: with a DispatchPlanner active, each demanded
        expert that is neither tier-0 resident nor findable locally —
        i.e. would be a peer fetch — is priced fetch-vs-ship on its token
        count. Shipped experts bypass the ExpertCache entirely (no
        access, no insert, no pin): their weights are staged in ephemeral
        slot rows modeling the peer's copy, the round trip is charged to
        the ship channel, and the SAME jitted expert program computes
        them — so streams stay token-identical while tier 0 is untouched.
        """
        ship_slot: Dict = {}
        if self.planner is not None:
            tok_count: Dict = {}
            for i in range(n_real):
                for e in np.unique(idx_np[i]):
                    key = (mi, int(e))
                    tok_count[key] = tok_count.get(key, 0) + 1
            for key, n_tok in sorted(tok_count.items()):
                if key in self.cache:
                    continue            # tier-0 resident: just compute
                if self.store.tier_of(key) != TIER_PEER:
                    continue            # local/disk: fetch path owns it
                if self.planner.choose(n_tok) != "ship":
                    continue
                wire = self.planner.ship_bytes(n_tok)
                peer_w = self.store.ship(key, n_tok, wire)
                ship_slot[key] = self.slots.fill_ship(len(ship_slot),
                                                      peer_w)
                self.tracker.submit(key, wire, tier=CHANNEL_SHIP,
                                    duration=self.planner.ship_s(n_tok),
                                    coalesce=False)
        tel_on = self.tel.enabled
        miss_tier: Dict = {}
        t01_hit = t01_miss = n_hit = n_miss = 0
        if tel_on:
            # predictor scoreboard: the raw distance-0 prediction captured
            # by _submit_prefetch vs the experts the router actually used
            # this layer visit (both as key sets, micro-counted)
            actual = {(mi, int(e)) for i in range(n_real)
                      for e in np.unique(idx_np[i])}
            pred = self._pred_d0.pop(mi, None)
            if pred is not None:
                pw = f1_over_window([{e for _, e in pred}],
                                    [{e for _, e in actual}])
                self.tel.predictor_window(pw.tp, pw.fp, pw.fn)
            # tier-of read-out BEFORE any access mutates residency: a
            # miss served from the store's tier-1 host cache still counts
            # toward the paper's tier-0/1 hit rate
            miss_tier = {k: self.store.tier_of(k) for k in actual
                         if k not in self.cache and k not in ship_slot}
        gts, pinned = [], []
        for i in range(n_real):                   # live units only
            gt = np.unique(idx_np[i])
            gts.append(gt)
            for e in gt:
                key = (mi, int(e))
                if key in ship_slot:
                    continue            # computed remotely this step
                hit = self.cache.access(key)
                self.stats.hits += int(hit)
                self.stats.misses += int(not hit)
                if tel_on:
                    n_hit += int(hit)
                    n_miss += int(not hit)
                    if hit or miss_tier.get(key) == TIER_HOST:
                        t01_hit += 1
                    else:
                        t01_miss += 1
                # pin immediately: a later unit's demand fetch must not
                # evict an expert this step still computes with
                self.cache.pin(key)
                pinned.append(key)
        if tel_on:
            if n_hit:
                self.tel.counter("cache.hit", n_hit)
            if n_miss:
                self.tel.counter("cache.miss", n_miss)
            if t01_hit:
                self.tel.counter("cache.t01_hit", t01_hit)
            if t01_miss:
                self.tel.counter("cache.t01_miss", t01_miss)
        self.tracker.wait({(mi, int(e)) for gt in gts for e in gt})
        slot_idx = np.zeros(idx_np.shape, np.int32)
        slot_table = self.slots.slot_of
        for i in range(n_real):
            slot_idx[i] = [
                ship_slot[key] if key in ship_slot else slot_table[key]
                for key in ((mi, int(e)) for e in idx_np[i])]
        # the call reads each distinct slot once: its sorted ids and each
        # live pair's position among them travel as one array
        slots = pack_slots(slot_idx, n_real)
        if tel_on:
            self.tel.counter("expert.pairs", n_real * idx_np.shape[1])
            self.tel.counter("expert.distinct",
                             np.unique(slot_idx[:n_real]).size)
        x = self._expert(h, w, jnp.asarray(slots), self.slots.w_gate,
                         self.slots.w_up, self.slots.w_down,
                         lp["moe"].get("shared"), x)
        for key in pinned:
            self.cache.unpin(key)
        self._advance(self.moe_layers[mi], 1)     # the expert-FFN half
        if self.scorer is not None:
            self.scorer.tick()    # one MoE layer computed == one clock unit
        return x, gts

    def _sync_stats(self):
        self.stats.fetch_bytes = self.slots.fetch_bytes
        self.stats.sim_stall_s = self.tracker.stall_s
        self.stats.blocking_stall_s = self.slots.sim_fetch_s
        self.stats.overlapped_s = self.tracker.overlapped_s
        self.stats.stall_by_tier = dict(self.tracker.stall_by_tier)
        self.stats.overlapped_by_tier = dict(self.tracker.overlapped_by_tier)
        self.stats.deep_prefetch_hits = self.cache.stats.deep_prefetch_hits
        self.stats.fetches_deduped = self.tracker.fetches_deduped
        self.stats.evictions_learned = self.cache.stats.evictions_learned
        self.stats.evictions_lru = self.cache.stats.evictions_lru
        st = getattr(self.store, "stats", None)
        if st is not None:
            self.stats.fetches_by_tier = dict(st.fetches_by_tier)
            self.stats.fetch_bytes_by_tier = dict(st.bytes_by_tier)
            self.stats.ships = st.ships
            self.stats.ship_bytes = st.ship_bytes
            self.stats.ship_tokens = st.ship_tokens
        elif self.slots.fetch_count:
            self.stats.fetches_by_tier = {TIER_HOST: self.slots.fetch_count}
            self.stats.fetch_bytes_by_tier = {TIER_HOST:
                                              self.slots.fetch_bytes}

    def step(self, caches, rows: Sequence[int], pos: Sequence[int],
             tokens: Sequence[int], policy: Optional[PerRequestPolicy],
             rids: Sequence[int], tables: Optional[np.ndarray] = None):
        """One decode step for N active requests (N <= max_batch).

        rows: KV-cache row per request; pos: per-request positions; tokens:
        token fed per request. With ``tables`` (N, W) int32 block tables,
        layers whose KV grows run through the paged pools (``tables`` row i
        must already cover position ``pos[i]``) while ring-buffer kinds keep
        using ``rows``; without it every layer uses contiguous rows. Returns
        (logits (N, V) f32, new caches, per-request per-MoE-layer
        ground-truth sets).
        """
        with self.tel.span(PID_ENGINE, 1, "decode_step",
                           {"batch": len(rows)}):
            return self._decode_step(caches, rows, pos, tokens, policy,
                                     rids, tables)

    def _decode_step(self, caches, rows, pos, tokens, policy, rids,
                     tables):
        cfg = self.cfg
        n = len(rows)
        ts = list(pos)
        nb = bucket_size(n, self.max_batch)
        pad = nb - n
        rows_p = jnp.asarray(list(rows) + [self.scratch_row] * pad, jnp.int32)
        pos_p = jnp.asarray(list(pos) + [0] * pad, jnp.int32)
        toks_p = jnp.asarray(list(tokens) + [0] * pad, jnp.int32)
        embeddings = self._tok_emb_np[np.asarray(tokens, np.int64)]
        if tables is not None:
            # pad lanes get all-scratch tables: their scatters land in the
            # scratch block, never a live request's pages
            tab_p = np.zeros((nb, tables.shape[1]), np.int32)
            tab_p[:n] = tables
            tab_p = jnp.asarray(tab_p)

        t_wall = time.perf_counter()
        self._step_advanced = 0.0
        x = self._embed(self._tok_emb, toks_p)
        experts_out = [[] for _ in range(n)]
        # double-buffer: predictions for the lookahead window starting at
        # the first MoE layer go onto the channels now, hiding behind the
        # dense/attention layers below it
        self._submit_prefetch(policy, rids, ts, 0)
        for li in range(cfg.num_layers):
            lp = self.layers[li]
            kind = self.kinds[li]
            if tables is not None and kind in T.PAGED_KINDS:
                x, caches[li] = self._paged_attn(lp, x, caches[li], tab_p,
                                                 pos_p, kind=kind,
                                                 kernel=self.kernel)
            else:
                x, caches[li] = self._attn(lp, x, caches[li], rows_p, pos_p,
                                           kind=kind)
            self._advance(li, 0)
            if li in self.moe_index:
                mi = self.moe_index[li]
                h, w, idx = self._router(lp["ln2"], lp["moe"]["w_router"], x)
                with self.tel.span(PID_ENGINE, 1, "driver.route_sync"):
                    idx_np = np.asarray(idx)[:, 0, :]           # (nb, k)
                x, gts = self._moe_units(mi, lp, h, w.astype(x.dtype), x,
                                         idx_np, n)
                if policy is not None:
                    policy.observe_batch(rids, ts, mi, gts, embeddings)
                for i in range(n):
                    experts_out[i].append(gts[i])
                # double-buffer the NEXT MoE layers' predicted experts
                self._submit_prefetch(policy, rids, ts, li + 1)
            elif "ffn" in lp:
                x = self._dense_ffn(lp, x)
                self._advance(li, 1)
        with self.tel.span(PID_ENGINE, 1, "driver.logits_sync"):
            logits = np.asarray(self._unembed(self._head_params, x))[:n, 0]
        self.stats.tokens += n
        self.stats.steps += 1
        wall = time.perf_counter() - t_wall
        self._calibrate(wall)
        self._sync_stats()
        if self.tel.enabled:
            t0_s = self.tel.rel(t_wall)
            self.tel.histogram("step.wall_s", wall)
            for rid, p in zip(rids, pos):
                tid = int(rid) + 1
                self.tel.ensure_track(PID_REQUESTS, tid, f"req {rid}")
                self.tel.complete(PID_REQUESTS, tid, "decode", t0_s, wall,
                                  {"pos": int(p)})
        return logits, caches, experts_out

    # ------------------------------------------------------------------
    def prefill_chunk(self, caches, table: np.ndarray, t0: int,
                      tokens: Sequence[int],
                      policy: Optional[PerRequestPolicy], rid: int):
        """One prompt chunk of a single request through the paged stack.

        tokens sit at absolute positions t0..t0+len(tokens)-1; ``table``
        (W,) int32 must already cover the last of them. The chunk is padded
        to a power-of-two bucket (compiled once per bucket, like decode
        padding buckets); per-token math is identical to feeding the same
        tokens one-by-one through the decode path, so chunked prefill keeps
        token-identical streams. ``t0`` may be nonzero with earlier
        positions' KV already in the table's blocks (later chunks, or a
        prefix-cache match skipping straight past the shared prefix).
        Returns (logits (len(tokens), V) f32, caches, experts) — experts is
        a per-MoE-layer list of per-token ground-truth expert-id arrays,
        the raw material the prefix cache records for activation replay.
        """
        with self.tel.span(PID_ENGINE, 1, "prefill_chunk",
                           {"rid": int(rid), "n": len(tokens)}):
            return self._prefill_chunk(caches, table, t0, tokens, policy,
                                       rid)

    def _prefill_chunk(self, caches, table, t0, tokens, policy, rid):
        assert self.chunk_prefill_ok, \
            "chunked prefill needs a global/mla-only stack"
        cfg = self.cfg
        n = len(tokens)
        assert 0 < n <= self.max_prefill_chunk
        cb = bucket_size(n, self.max_prefill_chunk)
        ts = list(range(t0, t0 + n))
        toks_p = jnp.asarray(list(tokens) + [0] * (cb - n), jnp.int32)
        tab = jnp.asarray(table, jnp.int32)
        embeddings = self._tok_emb_np[np.asarray(tokens, np.int64)]

        t_wall = time.perf_counter()
        self._step_advanced = 0.0
        x = self._embed_seq(self._tok_emb, toks_p)               # (1,cb,D)
        experts_out: List[List[np.ndarray]] = []
        self._submit_prefetch(policy, [rid], [t0], 0)
        for li in range(cfg.num_layers):
            lp = self.layers[li]
            # lint: disable=bucket-discipline -- t0/n_valid trace as shape-()
            # weak-typed scalars (one compile covers every value); the chunk
            # array itself is padded to a pow-2 bucket via bucket_size above
            x, caches[li] = self._paged_prefill(lp, x, caches[li], tab, t0,
                                                n, kind=self.kinds[li],
                                                kernel=self.kernel)
            self._advance(li, 0)
            if li in self.moe_index:
                mi = self.moe_index[li]
                h, w, idx = self._router(lp["ln2"], lp["moe"]["w_router"],
                                         x)                     # (1,cb,...)
                with self.tel.span(PID_ENGINE, 1, "driver.route_sync"):
                    idx_np = np.asarray(idx)[0]                 # (cb, k)
                # chunk tokens become the expert units: same expert program,
                # same pinning discipline as decode lanes
                hu = h[0][:, None, :]
                wu = w[0][:, None, :].astype(x.dtype)
                xu = x[0][:, None, :]
                xu, gts = self._moe_units(mi, lp, hu, wu, xu, idx_np, n)
                x = xu[:, 0, :][None]
                experts_out.append(gts)
                if policy is not None:
                    policy.observe_batch([rid] * n, ts, mi, gts, embeddings)
                self._submit_prefetch(policy, [rid], [t0 + n - 1], li + 1)
            elif "ffn" in lp:
                x = self._dense_ffn(lp, x)
                self._advance(li, 1)
        with self.tel.span(PID_ENGINE, 1, "driver.logits_sync"):
            logits = np.asarray(self._unembed(self._head_params, x))[0, :n]
        self.stats.tokens += n
        self.stats.prefill_tokens += n
        self.stats.prefill_chunks += 1
        wall = time.perf_counter() - t_wall
        self._calibrate(wall)
        self._sync_stats()
        if self.tel.enabled:
            t0_s = self.tel.rel(t_wall)
            tid = int(rid) + 1
            self.tel.ensure_track(PID_REQUESTS, tid, f"req {rid}")
            self.tel.complete(PID_REQUESTS, tid, "prefill", t0_s, wall,
                              {"t0": int(t0), "n": n})
            self.tel.histogram("prefill.wall_s", wall)
        return logits, caches, experts_out


class OffloadEngine:
    """Batch-1 engine: the original public API on the shared DecodeCore."""

    def __init__(self, model, params, policy: Optional[Policy],
                 capacity: int, eviction: str = "lru",
                 host_bw: float = 100e9,
                 expert_backend: Optional[str] = None,
                 layer_compute_s: Union[float, str] = 0.0, tiers=None,
                 telemetry=None):
        self.core = DecodeCore(model, params, capacity, eviction, host_bw,
                               expert_backend, max_batch=1,
                               layer_compute_s=layer_compute_s, tiers=tiers,
                               telemetry=telemetry)
        self.cfg = self.core.cfg
        self.model = model
        self.policy = policy
        # a single in-flight request may share one stateful instance
        self._prp = (None if policy is None
                     else PerRequestPolicy(policy, force_shared=True))

    @property
    def stats(self) -> EngineStats:
        return self.core.stats

    @property
    def cache(self):
        return self.core.cache

    @property
    def slots(self):
        return self.core.slots

    @property
    def store(self):
        return self.core.store

    @property
    def layers(self):
        return self.core.layers

    def init_state(self, cache_len: int):
        return {"pos": 0, "caches": self.core.alloc_caches(cache_len)}

    def decode_token(self, state, token: int):
        """One token through all layers; returns (logits, state, experts)."""
        logits, caches, experts = self.core.step(
            state["caches"], rows=[0], pos=[state["pos"]],
            tokens=[int(token)], policy=self._prp, rids=[0])
        state["caches"] = caches
        state["pos"] = state["pos"] + 1
        return logits[0], state, experts[0]

    def generate(self, prompt, max_new: int, cache_len: int,
                 temperature: float = 0.0, seed: int = 0):
        if len(prompt) == 0:
            raise ValueError(
                "empty prompt: generation needs at least one token to seed "
                "the decode loop")
        if max_new < 0:
            raise ValueError(f"max_new must be >= 0, got {max_new}")
        state = self.init_state(cache_len)
        if self._prp is not None:
            self._prp.begin_request(0)
        rng = np.random.default_rng(seed)
        cur = prompt[0]
        n_total = min(len(prompt) + max_new, cache_len)
        generated = []
        for t in range(n_total):
            logits, state, _ = self.decode_token(state, int(cur))
            if t + 1 < len(prompt):
                cur = prompt[t + 1]
            else:
                cur = sample_token(logits, temperature, rng)
                generated.append(cur)
        return generated

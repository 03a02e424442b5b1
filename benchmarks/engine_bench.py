"""Beyond-paper: the integrated offload serving engine (real decode, real
slot buffer) under each prefetch policy — hit rates + modeled stall — plus
batched-vs-sequential decode throughput for the continuous-batching engine.

CI smoke mode (no cached artifacts, tiny backbone, JSON artifact):
  PYTHONPATH=src python -m benchmarks.engine_bench --tiny \
      --out artifacts/engine_bench.json

Mixed-length workload mode (--mixed): ragged prompts at batch >= 4 through
the paged + chunked-prefill engine vs the token-by-token prompt path —
reports per-request admission-to-first-token latency and the KV memory
high-water (actual blocks allocated vs the contiguous batch x cache_len
model):
  PYTHONPATH=src python -m benchmarks.engine_bench --tiny --mixed \
      --out artifacts/engine_bench_mixed.json

Long-context mode (--longctx): sweeps simulated cache length and times one
batched decode step through the paged pools on the flash-decode kernel
route vs the gather-and-materialise route, reporting per-step latency,
modeled KV bytes read, and the (N, W*block_size, ...) bytes only the gather
route materialises:
  PYTHONPATH=src python -m benchmarks.engine_bench --tiny --longctx \
      --out artifacts/engine_bench_longctx.json

Prefix-sharing mode (--prefix): N requests sharing a >=64-token system
prompt through the paged engine with the prefix cache on vs off — reports
the prefix hit rate, skipped-prefill tokens, per-request TTFT, and the KV
block high-water (streams must stay token-identical; TTFT and high-water
must drop):
  PYTHONPATH=src python -m benchmarks.engine_bench --tiny --prefix \
      --out artifacts/engine_bench_prefix.json

Tiered-store mode (--tiers): the expert set sharded across simulated hosts
with disk spill (serving/expertstore.py) — sweeps shard count x tier-0
capacity reporting per-tier hit rates, the stall-by-tier breakdown, and
tok/s, then pins horizon-aware prefetch against fixed-horizon at equal
tier-0 capacity (streams must stay token-identical to the single-host
engine; horizon-aware must shrink un-overlapped stall). ``--dispatch all``
adds the fetch/ship/auto compute-dispatch comparison (ship the token group
to the expert's shard vs pull its weights) in a cold-expert regime:
  PYTHONPATH=src python -m benchmarks.engine_bench --tiny --tiers \
      --dispatch all --out artifacts/engine_bench_tiers.json

SLO mode (--slo): an open-loop Poisson load sweep (serving/workload.py) of
an interactive class (urgent, tight TTFT SLO) mixed with long batch
requests, served with SLO-aware preemptive scheduling on vs off — reports
p50/p95/p99 TTFT, per-token latency, preemption counts, and goodput under
SLO per arrival rate, and asserts that preemption beats the no-preemption
baseline on p99 TTFT AND goodput at >=1 overload point with every stream
token-identical to an uncontended reference run:
  PYTHONPATH=src python -m benchmarks.engine_bench --tiny --slo \
      --out artifacts/engine_bench_slo.json

Telemetry-trace mode (--trace): the tiered paged engine with the runtime
telemetry layer (serving/telemetry.py) on — per-request span timelines,
copy-channel transfer tracks, and the predictor-quality scoreboard —
pinned token-identical and deterministic-stats-identical against a
telemetry-off twin, written as Chrome trace_event JSON that opens in
ui.perfetto.dev (tools/check_trace.py validates it in CI):
  PYTHONPATH=src python -m benchmarks.engine_bench --tiny --trace \
      --out artifacts/engine_bench_trace.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


class _SanitizerSession:
    """--sanitize support: one :class:`~repro.analysis.RetraceGuard` around
    the whole run (per-program XLA compile counts in the artifact) plus a
    :class:`~repro.analysis.LeakSanitizer` auto-installed on every batched
    engine the run constructs, so the pool refcount ledger and the expert
    store's residency ledger are re-proved at every request retire."""

    def __init__(self):
        from repro.analysis import RetraceGuard
        self.guard = RetraceGuard()
        self.sanitizers = []
        self._orig_init = None

    def __enter__(self):
        from repro.analysis import sanitize_engine
        from repro.serving.scheduler import BatchedOffloadEngine
        self.guard.__enter__()
        orig = BatchedOffloadEngine.__init__
        sanitizers = self.sanitizers

        def init_with_sanitizer(eng, *a, **kw):
            orig(eng, *a, **kw)
            san = sanitize_engine(eng)
            if san is not None:
                sanitizers.append(san)

        self._orig_init = orig
        BatchedOffloadEngine.__init__ = init_with_sanitizer
        return self

    def __exit__(self, *exc):
        from repro.serving.scheduler import BatchedOffloadEngine
        if self._orig_init is not None:
            BatchedOffloadEngine.__init__ = self._orig_init
            self._orig_init = None
        for san in self.sanitizers:
            san.uninstall()
        self.guard.__exit__(*exc)

    def report(self) -> dict:
        """The ``"sanitizer"`` artifact section."""
        counts = self.guard.counts()
        return {
            "compiles_by_program": counts,
            "distinct_programs": len(counts),
            "total_compiles": sum(counts.values()),
            "engines_sanitized": len(self.sanitizers),
            "leak_checks": sum(s.checks for s in self.sanitizers),
        }


def _throughput(model, params, cfg, prompts, max_new: int, cache_len: int,
                batch: int, log=print):
    """tokens/s: one batched engine at ``batch`` vs the same requests run
    sequentially through one batch-1 engine. Both are warmed first so jit
    compilation stays out of the timed region."""
    from repro.core.tracing import moe_layer_ids
    from repro.serving.engine import OffloadEngine
    from repro.serving.scheduler import BatchedOffloadEngine

    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts

    seq = OffloadEngine(model, params, None, n_total)
    seq.generate(prompts[0], max_new=2, cache_len=cache_len)      # warm
    tok0 = seq.stats.tokens
    t0 = time.perf_counter()
    for p in prompts:
        seq.generate(p, max_new=max_new, cache_len=cache_len)
    seq_s = time.perf_counter() - t0
    seq_tokens = seq.stats.tokens - tok0

    bat = BatchedOffloadEngine(model, params, None, n_total,
                               max_batch=batch)
    bat.generate(prompts, max_new=2, cache_len=cache_len)         # warm
    tok0 = bat.stats.tokens
    t0 = time.perf_counter()
    bat.generate(prompts, max_new=max_new, cache_len=cache_len)
    bat_s = time.perf_counter() - t0
    bat_tokens = bat.stats.tokens - tok0

    seq_tps = seq_tokens / max(seq_s, 1e-9)
    bat_tps = bat_tokens / max(bat_s, 1e-9)
    log(f"  throughput: sequential {seq_tps:.1f} tok/s, "
        f"batch={batch} {bat_tps:.1f} tok/s "
        f"({bat_tps / max(seq_tps, 1e-9):.2f}x, "
        f"mean batch {bat.stats.mean_batch:.2f})")
    return {"seq_tok_s": seq_tps, "batched_tok_s": bat_tps,
            "speedup": bat_tps / max(seq_tps, 1e-9),
            "mean_batch": bat.stats.mean_batch}


def _mixed_workload(cfg, corpus, n_requests: int, seed: int):
    """Ragged prompt lengths spanning sub-block to multi-block: the shape
    continuous batching actually sees."""
    from repro.data import sample_prompts
    lengths = [4, 28, 8, 36, 6, 20, 32, 12][:n_requests]
    rng_seed = seed
    prompts = []
    for i, ln in enumerate(lengths):
        prompts.append(sample_prompts(corpus, 1, ln, seed=rng_seed + i)[0])
    return prompts


def _mixed_latency(model, params, cfg, prompts, max_new: int, cache_len: int,
                   batch: int, log=print):
    """Admission-to-first-token latency + KV memory high-water: paged engine
    with chunked prefill vs the same engine on the token-by-token prompt
    path (paged=False), same requests, batch >= 4."""
    from repro.core.tracing import moe_layer_ids
    from repro.serving.scheduler import BatchedOffloadEngine

    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts

    def ttft_stats(eng, rid_from):
        # only the timed run's requests: the warm run's first tokens pay
        # jit compilation and would swamp the scheduling signal
        tt = sorted(v for r, v in eng.ttft().items() if r >= rid_from)
        if not tt:
            return {"mean": 0.0, "p50": 0.0, "max": 0.0}
        return {"mean": float(sum(tt) / len(tt)),
                "p50": float(tt[len(tt) // 2]),
                "max": float(tt[-1])}

    tok = BatchedOffloadEngine(model, params, None, n_total,
                               max_batch=batch, paged=False)
    tok.generate(prompts, max_new=2, cache_len=cache_len)            # warm
    rid0 = tok._next_rid
    t0 = time.perf_counter()
    outs_tok = tok.generate(prompts, max_new=max_new, cache_len=cache_len)
    tok_s = time.perf_counter() - t0
    tok_tt = ttft_stats(tok, rid0)

    pag = BatchedOffloadEngine(model, params, None, n_total,
                               max_batch=batch, block_size=8,
                               prefill_chunk=16)
    pag.generate(prompts, max_new=2, cache_len=cache_len)            # warm
    rid0 = pag._next_rid
    chunks0, ptok0 = pag.stats.prefill_chunks, pag.stats.prefill_tokens
    t0 = time.perf_counter()
    outs_pag = pag.generate(prompts, max_new=max_new, cache_len=cache_len)
    pag_s = time.perf_counter() - t0
    pag_tt = ttft_stats(pag, rid0)

    assert outs_pag == outs_tok, "paged/token prompt paths diverged"

    # memory model: actual paged high-water vs contiguous batch x cache_len
    per_tok = pag.kv_block_bytes / pag.block_size
    rows_bytes = int(batch * cache_len * per_tok)
    paged_bytes = pag.kv_high_water_bytes
    log(f"  mixed-length batch={batch}: TTFT mean "
        f"{tok_tt['mean'] * 1e3:.1f}ms token-path vs "
        f"{pag_tt['mean'] * 1e3:.1f}ms paged+chunked "
        f"({tok_tt['mean'] / max(pag_tt['mean'], 1e-9):.2f}x); KV high-water "
        f"{paged_bytes / 2**10:.0f}KiB paged vs {rows_bytes / 2**10:.0f}KiB "
        f"batch*cache_len rows "
        f"({paged_bytes / max(rows_bytes, 1):.2f}x)")
    # prompt tokens each engine streamed token-by-token through decode:
    # ~every prompt body on the token path, none on the chunk-prefill path
    # (ring/recurrent stacks would show up here even with paged=True)
    log(f"  fallback prefill tokens: {tok.stats.fallback_prefill_tokens} "
        f"token-path vs {pag.stats.fallback_prefill_tokens} paged+chunked")
    return {
        "ttft_token_mean_s": tok_tt["mean"],
        "ttft_token_p50_s": tok_tt["p50"],
        "ttft_token_max_s": tok_tt["max"],
        "ttft_paged_mean_s": pag_tt["mean"],
        "ttft_paged_p50_s": pag_tt["p50"],
        "ttft_paged_max_s": pag_tt["max"],
        "ttft_speedup": tok_tt["mean"] / max(pag_tt["mean"], 1e-9),
        "wall_token_s": tok_s,
        "wall_paged_s": pag_s,
        "kv_high_water_bytes": paged_bytes,
        "kv_rows_model_bytes": rows_bytes,
        "kv_high_water_frac": paged_bytes / max(rows_bytes, 1),
        "kv_blocks_high_water": pag.pool.stats.high_water,
        "prefill_chunks": pag.stats.prefill_chunks - chunks0,
        "prefill_tokens": pag.stats.prefill_tokens - ptok0,
        "fallback_prefill_tokens_token_path": tok.stats.fallback_prefill_tokens,
        "fallback_prefill_tokens_paged": pag.stats.fallback_prefill_tokens,
        "streams_identical": True,
    }


def _prefix_workload(cfg, corpus, n_requests: int, sys_len: int,
                     tail_len: int, seed: int):
    """N prompts sharing one ``sys_len``-token system prompt with unique
    ``tail_len``-token user tails — the shape prefix sharing targets."""
    from repro.data import sample_prompts
    system = sample_prompts(corpus, 1, sys_len, seed=seed)[0]
    tails = [sample_prompts(corpus, 1, tail_len, seed=seed + 1 + i)[0]
             for i in range(n_requests)]
    return [list(system) + list(t) for t in tails]


def _prefix_sharing(model, params, cfg, prompts, shared_len: int,
                    max_new: int, cache_len: int, batch: int,
                    block_size: int, log=print):
    """Prefix cache on vs off on a shared-system-prompt workload: streams
    must stay token-identical while TTFT and the KV block high-water drop
    and the hit counters show real skipped prefill."""
    from repro.core.tracing import moe_layer_ids
    from repro.serving.scheduler import BatchedOffloadEngine

    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts

    def ttft_mean(eng, rid_from):
        tt = [v for r, v in eng.ttft().items() if r >= rid_from]
        return float(sum(tt) / len(tt)) if tt else 0.0

    engines = {}
    for name, share in (("off", False), ("on", True)):
        eng = BatchedOffloadEngine(model, params, None, n_total,
                                   max_batch=batch, block_size=block_size,
                                   prefill_chunk=16, prefix_cache=share)
        # warm jit outside the timed region (the warm run's prefix index is
        # per-run state and is rebuilt from scratch by the timed run)
        eng.generate(prompts[:2], max_new=2, cache_len=cache_len)
        engines[name] = eng

    out = {}
    streams = {}
    for name, eng in engines.items():
        rid0 = eng._next_rid
        ptok0 = eng.stats.prefill_tokens
        t0 = time.perf_counter()
        streams[name] = eng.generate(prompts, max_new=max_new,
                                     cache_len=cache_len)
        out[f"wall_{name}_s"] = time.perf_counter() - t0
        out[f"ttft_{name}_mean_s"] = ttft_mean(eng, rid0)
        out[f"kv_blocks_high_water_{name}"] = eng.pool.stats.high_water
        out[f"prefill_tokens_{name}"] = eng.stats.prefill_tokens - ptok0

    assert streams["on"] == streams["off"], \
        "prefix sharing changed a token stream"
    eng = engines["on"]
    st = eng.prefix.stats
    eng.pool.check_leaks(expected_in_use=eng.prefix.cached_blocks)
    out.update({
        "streams_identical": True,
        "n_requests": len(prompts),
        "shared_prefix_tokens": shared_len,
        "prefix_hit_rate": st.hit_rate,
        "prefix_hits": st.hits,
        "prefix_extensions": st.extensions,
        "skipped_prefill_tokens": st.hit_tokens,
        "prefix_cached_blocks": eng.prefix.cached_blocks,
        "cow_copies": eng.pool.stats.cow_copies,
        "ttft_speedup": (out["ttft_off_mean_s"]
                         / max(out["ttft_on_mean_s"], 1e-9)),
        "kv_high_water_frac": (out["kv_blocks_high_water_on"]
                               / max(out["kv_blocks_high_water_off"], 1)),
    })
    log(f"  prefix sharing batch={batch}: hit rate "
        f"{st.hit_rate:.2f} ({st.hits} hits + {st.extensions} boundary "
        f"extensions), {st.hit_tokens} prompt tokens skipped, "
        f"{out['cow_copies']} COW copies")
    log(f"  TTFT mean {out['ttft_off_mean_s'] * 1e3:.1f}ms off -> "
        f"{out['ttft_on_mean_s'] * 1e3:.1f}ms on "
        f"({out['ttft_speedup']:.2f}x); KV high-water "
        f"{out['kv_blocks_high_water_off']} -> "
        f"{out['kv_blocks_high_water_on']} blocks "
        f"({out['kv_high_water_frac']:.2f}x)")
    return out


def _tier_sweep(model, params, cfg, prompts, max_new: int, cache_len: int,
                batch: int, replacement: str = "both",
                cold_dtype: str = "both", dispatch: str = "fetch",
                log=print):
    """Tiered expert store under load: shard count x tier-0 capacity sweep
    (per-tier hit rates, stall-by-tier, tok/s), then horizon-aware vs
    fixed-horizon prefetch at equal tier-0 capacity, then learned-vs-LRU
    replacement and int8-vs-full cold tiers side by side.

    The tier hardware model is scaled to the architecture's own roofline
    (layer_compute_s="roofline" drives the OverlapTracker clock): a tier-2
    fetch costs ~1.2 layers of compute, a tier-3 fetch ~2.5 — so a
    single-layer lookahead cannot hide the slow tiers but a tier-scaled
    horizon can. Every full-precision configuration's streams must be
    token-identical to the single-host engine's; the lossy int8 run
    reports (not asserts) whether its streams matched.

    ``replacement`` in {"lru", "learned", "both"} picks the eviction
    policies swept; ``cold_dtype`` in {"none", "int8", "both"} picks the
    cold-tier storage comparison; ``dispatch`` in {"fetch", "ship",
    "auto", "all"} additionally compares compute-dispatch modes in a
    cold-expert regime (no tier-1 promotion cache, slow interconnect,
    equal tier-0 capacity): ships vs fetches, wire bytes down each path,
    and un-overlapped stall — asserting auto strictly beats fetch-only
    on stall with token-identical streams."""
    from repro.core.policies import NextLayerAllPolicy
    from repro.core.tracing import moe_layer_ids
    from repro.launch.dryrun import decode_layer_roofline
    from repro.serving.expertstore import TierConfig
    from repro.serving.scheduler import BatchedOffloadEngine

    n_moe = len(moe_layer_ids(cfg))
    e = cfg.moe.num_experts
    n_total = n_moe * e
    pol = NextLayerAllPolicy(e)

    # single-host reference: same requests, same policy, one DRAM store
    ref = BatchedOffloadEngine(model, params, pol, n_total, max_batch=batch)
    ref_out = ref.generate(prompts, max_new=max_new, cache_len=cache_len)
    expert_bytes = ref.core.store.bytes_per_expert

    per_layer = decode_layer_roofline(cfg, batch=batch)
    mean_layer = sum(a + f for a, f in per_layer) / len(per_layer)

    def tier_cfg(shards, horizons=(1, 1, 2, 3), cache_experts=None,
                 cold=None):
        # scale the tier hardware model so one MoE layer's *batch* of
        # peer/disk fetches costs ~1.5/~2.2 layers of this arch's roofline
        # compute: a single-layer lookahead cannot hide the slow tiers,
        # a tier-scaled one can
        dram = max(1, n_total // (shards * 4))
        disk_per_layer = max(1, (n_total - shards * dram) // n_moe)
        peer_per_layer = max(1, (shards - 1) * dram // n_moe)
        dur_disk = 2.2 * mean_layer / disk_per_layer
        dur_peer = 1.5 * mean_layer / peer_per_layer
        return TierConfig(
            num_shards=shards,
            shard_dram_experts=dram,
            cache_experts=(max(2, n_total // 6) if cache_experts is None
                           else cache_experts),
            peer_latency_s=0.3 * dur_peer,
            peer_bw=expert_bytes / (0.7 * dur_peer),
            disk_latency_s=0.3 * dur_disk,
            disk_bw=expert_bytes / (0.7 * dur_disk),
            horizons=horizons,
            cold_dtype=cold)

    # local DRAM is an order faster than the interconnect: a full layer's
    # worth of tier-1 refetches costs ~0.4 layers of compute, so a
    # single-layer lookahead hides them (tier-1 duration is modeled by the
    # SlotBuffer's host_bw, not TierConfig)
    host_bw = expert_bytes * e / (0.4 * mean_layer)

    def run_engine(tc, cap, eviction="lru", assert_parity=True):
        eng = BatchedOffloadEngine(model, params, pol, cap,
                                   eviction=eviction, host_bw=host_bw,
                                   max_batch=batch,
                                   layer_compute_s="roofline", tiers=tc)
        t0 = time.perf_counter()
        out = eng.generate(prompts, max_new=max_new, cache_len=cache_len)
        wall = time.perf_counter() - t0
        if assert_parity:
            assert out == ref_out, "tiered store changed a token stream"
        s = eng.stats
        st = eng.core.store.stats
        accesses = max(s.hits + s.misses, 1)
        slow = (s.fetches_by_tier.get(2, 0) + s.fetches_by_tier.get(3, 0))
        fast = s.hits + s.fetches_by_tier.get(1, 0)
        row = {
            "replacement": eviction,
            "tok_s": s.tokens / max(wall, 1e-9),
            "tier0_hit_rate": s.hit_rate,
            # share of expert materialisations served without touching a
            # slow tier: tier-0 slot hits plus tier-1 (local DRAM) fetches
            # over everything including tier-2/3 fetches
            "tier01_hit_rate": fast / max(fast + slow, 1),
            "tier_fetch_rates": {t: n / accesses
                                 for t, n in s.fetches_by_tier.items()},
            "fetches_by_tier": dict(s.fetches_by_tier),
            "fetch_bytes_by_tier": dict(s.fetch_bytes_by_tier),
            "stall_by_tier_ms": {t: v * 1e3
                                 for t, v in s.stall_by_tier.items()},
            "sim_stall_ms": s.sim_stall_s * 1e3,
            "overlapped_ms": s.overlapped_s * 1e3,
            "deep_prefetch_hits": s.deep_prefetch_hits,
            "horizon_clamps": s.horizon_clamps,
            "evictions_learned": s.evictions_learned,
            "evictions_lru": s.evictions_lru,
            "store_evictions_learned": st.cache_evictions_learned,
            "store_evictions_lru": st.cache_evictions_lru,
            "quantized_fetches": st.quantized_fetches,
            "spilled_experts": st.spilled_experts,
            "streams_match_ref": out == ref_out,
        }
        eng.core.store.close()
        return row

    reps = ("lru", "learned") if replacement == "both" else (replacement,)
    min_cap = batch * cfg.moe.top_k
    caps = sorted({max(min_cap, n_total // 3), n_total})
    sweep = []
    log(f"  tiers sweep ({n_total} experts, {e}/layer x {n_moe} layers): "
        "shards,cap,policy,tok/s,tier0_hit,tier01_hit,fetch_t1/t2/t3,"
        "stall_ms(t1/t2/t3)")
    for shards in (1, 4):
        for cap in caps:
            for rep in reps:
                row = {"num_shards": shards, "tier0_capacity": cap}
                row.update(run_engine(tier_cfg(shards), cap, eviction=rep))
                sweep.append(row)
                f = row["fetches_by_tier"]
                st = row["stall_by_tier_ms"]
                log(f"  {shards},{cap},{rep},{row['tok_s']:.1f},"
                    f"{row['tier0_hit_rate']:.3f},"
                    f"{row['tier01_hit_rate']:.3f},"
                    f"{f.get(1, 0)}/{f.get(2, 0)}/{f.get(3, 0)},"
                    f"{st.get(1, 0.0):.2f}/{st.get(2, 0.0):.2f}/"
                    f"{st.get(3, 0.0):.2f}")

    # horizon-aware vs fixed-horizon at equal tier-0 capacity. Compared at
    # the capacity that holds the lookahead window's working set: deeper
    # prefetch trades slot residency time for overlap, so at the bare
    # admission minimum it thrashes instead (visible in the sweep rows) —
    # tier-0 capacity and prefetch horizon are coupled knobs.
    cap = caps[-1]
    fixed = run_engine(tier_cfg(4, horizons=(1, 1, 1, 1)), cap)
    aware = run_engine(tier_cfg(4, horizons=(1, 1, 2, 3)), cap)
    reduction = 1.0 - (aware["sim_stall_ms"]
                       / max(fixed["sim_stall_ms"], 1e-12))
    log(f"  horizon-aware vs fixed (4 shards, cap {cap}): stall "
        f"{fixed['sim_stall_ms']:.2f} -> {aware['sim_stall_ms']:.2f} ms "
        f"({reduction:.1%} less), deep prefetch hits "
        f"{aware['deep_prefetch_hits']}")

    results = {
        "sweep": sweep,
        "streams_identical": True,
        "num_experts_total": n_total,
        "expert_bytes": expert_bytes,
        "mean_layer_roofline_s": mean_layer,
        "horizon_fixed": fixed,
        "horizon_aware": aware,
        "horizon_stall_reduction": reduction,
        "batch": batch,
        "replacement_axis": list(reps),
        "cold_dtype_axis": cold_dtype,
    }

    # learned vs LRU replacement at equal capacity, with a tier-1 cache
    # sized where retention matters (half the expert set): the scorer
    # keeps the copies predicted soonest-reused where LRU cycles them out
    if len(reps) == 2:
        cmp_cap = max(min_cap, n_total // 3)
        cmp_tc = lambda: tier_cfg(4, cache_experts=n_total // 2)  # noqa: E731
        cmp = {rep: run_engine(cmp_tc(), cmp_cap, eviction=rep)
               for rep in reps}
        hit_gain = (cmp["learned"]["tier01_hit_rate"]
                    - cmp["lru"]["tier01_hit_rate"])
        stall_red = 1.0 - (cmp["learned"]["sim_stall_ms"]
                           / max(cmp["lru"]["sim_stall_ms"], 1e-12))
        log(f"  learned vs lru (4 shards, cap {cmp_cap}, cache "
            f"{n_total // 2}): tier0+1 hit "
            f"{cmp['lru']['tier01_hit_rate']:.3f} -> "
            f"{cmp['learned']['tier01_hit_rate']:.3f} (+{hit_gain:.3f}), "
            f"stall {cmp['lru']['sim_stall_ms']:.2f} -> "
            f"{cmp['learned']['sim_stall_ms']:.2f} ms "
            f"({stall_red:.1%} less)")
        results["replacement_comparison"] = {
            "tier0_capacity": cmp_cap,
            "cache_experts": n_total // 2,
            "lru": cmp["lru"],
            "learned": cmp["learned"],
            "tier01_hit_gain": hit_gain,
            "stall_reduction": stall_red,
        }

    # int8 cold tiers vs full precision: same config, same requests —
    # tier-2/3 fetch bytes shrink by the quantization ratio. Lossy, so
    # stream parity is reported, not asserted.
    if cold_dtype in ("int8", "both"):
        full = run_engine(tier_cfg(4), cap)
        cold = run_engine(tier_cfg(4, cold=("int8")), cap,
                          assert_parity=False)
        b_full = sum(full["fetch_bytes_by_tier"].get(t, 0) for t in (2, 3))
        b_cold = sum(cold["fetch_bytes_by_tier"].get(t, 0) for t in (2, 3))
        ratio = b_full / max(b_cold, 1)
        log(f"  int8 cold tiers (4 shards, cap {cap}): tier-2/3 fetch "
            f"bytes {b_full / 2**20:.2f} -> {b_cold / 2**20:.2f} MiB "
            f"({ratio:.2f}x smaller), quantized fetches "
            f"{cold['quantized_fetches']}, streams match: "
            f"{cold['streams_match_ref']}")
        results["cold_comparison"] = {
            "tier0_capacity": cap,
            "full": full,
            "int8": cold,
            "cold_fetch_bytes_t23": b_cold,
            "full_fetch_bytes_t23": b_full,
            "cold_fetch_bytes_ratio_t23": ratio,
            "cold_streams_match": cold["streams_match_ref"],
        }

    # fetch vs ship vs auto compute dispatch in a cold-expert regime: no
    # tier-1 promotion cache, tier-0 sized to the bare demand window, and
    # an interconnect where one peer weight pull costs ~1.2 layers of
    # compute — every peer expert is a fresh per-(expert, token-count)
    # decision between pulling its weights and shipping its token group.
    if dispatch != "fetch":
        modes = (("fetch", "ship", "auto") if dispatch == "all"
                 else ("fetch", dispatch))
        dcap = min_cap
        dur_peer = 1.2 * mean_layer
        disp = {}
        log(f"  dispatch comparison (4 shards, cap {dcap}, cold peers): "
            "mode,tok_s,ships,fetches,ship_wire_KiB,fetch_wire_MiB,"
            "stall_ms")
        for mode in modes:
            tc = TierConfig(num_shards=4, cache_experts=0,
                            peer_latency_s=0.3 * dur_peer,
                            peer_bw=expert_bytes / (0.7 * dur_peer),
                            dispatch=mode)
            eng = BatchedOffloadEngine(model, params, pol, dcap,
                                       host_bw=host_bw, max_batch=batch,
                                       layer_compute_s="roofline",
                                       tiers=tc)
            t0 = time.perf_counter()
            out = eng.generate(prompts, max_new=max_new,
                               cache_len=cache_len)
            wall = time.perf_counter() - t0
            assert out == ref_out, \
                f"dispatch={mode} changed a token stream"
            s = eng.stats
            row = dict(eng.dispatch_summary())
            row.update({
                "tok_s": s.tokens / max(wall, 1e-9),
                "sim_stall_ms": s.sim_stall_s * 1e3,
                "stall_by_tier_ms": {t: v * 1e3
                                     for t, v in s.stall_by_tier.items()},
                "streams_match_ref": True,
            })
            disp[mode] = row
            eng.core.store.close()
            log(f"  {mode},{row['tok_s']:.1f},{row['ships']},"
                f"{row['fetches']},{row['ship_wire_bytes'] / 2**10:.1f},"
                f"{row['fetch_wire_bytes'] / 2**20:.2f},"
                f"{row['sim_stall_ms']:.2f}")
        for mode in modes[1:]:
            assert disp[mode]["ships"] > 0, f"{mode} mode never shipped"
        if "auto" in disp:
            # the acceptance: at equal tier-0 capacity, pricing fetch vs
            # ship per (expert, token-count) strictly cuts un-overlapped
            # stall vs always pulling weights
            assert (disp["auto"]["sim_stall_ms"]
                    < disp["fetch"]["sim_stall_ms"]), \
                "auto dispatch did not reduce stall vs fetch-only"
            red = 1.0 - (disp["auto"]["sim_stall_ms"]
                         / max(disp["fetch"]["sim_stall_ms"], 1e-12))
            log(f"  auto vs fetch-only: stall "
                f"{disp['fetch']['sim_stall_ms']:.2f} -> "
                f"{disp['auto']['sim_stall_ms']:.2f} ms ({red:.1%} less)")
            results["dispatch_stall_reduction"] = red
        results["dispatch_comparison"] = {
            "tier0_capacity": dcap,
            "modes": list(modes),
            "streams_identical": True,
            **disp,
        }
    return results


def _slo_sweep(model, params, cfg, n_requests: int, load_factors,
               log=print):
    """Open-loop Poisson load sweep with SLO-aware preemption on vs off.

    Two priority classes share the engine: "interactive" (urgent: short
    prompts, tight TTFT SLO measured in decode-program times so the budget
    tracks the machine) and "batch" (long prompts + long decode, no SLO).
    At each arrival rate the SAME workload is replayed through a FIFO
    engine and a preemptive engine; both must produce streams
    token-identical to an uncontended closed-loop reference. At >=1
    overload point the preemptive engine must beat FIFO on the urgent
    class's p99 TTFT AND on goodput-under-SLO — the acceptance this mode
    pins in CI."""
    from repro.core.metrics import latency_stats
    from repro.core.tracing import moe_layer_ids
    from repro.serving.config import ServeConfig
    from repro.serving.scheduler import BatchedOffloadEngine
    from repro.serving.workload import (SLO, PriorityClass, poisson_workload,
                                        scale_rate)

    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    cache_len, batch, bs = 96, 2, 8

    def build(preempt):
        sc = ServeConfig(max_batch=batch, block_size=bs, prefill_chunk=8,
                         prefix_cache=True, preemption=preempt)
        return BatchedOffloadEngine(model, params, None, n_total, serve=sc)

    engines = {"fifo": build(False), "preempt": build(True)}
    for eng in engines.values():
        # warm every bucket the sweep will hit — batch-class long prefill,
        # interactive-class short prefill, 1- and 2-lane decode, and the
        # 1/2/4-wide prefill tails a preemption resume can produce — so
        # compile time never lands in a measured run
        eng.generate([list(range(1, 33)), [3, 5, 7, 9, 2, 4]], max_new=24,
                     cache_len=cache_len)
        eng.generate([[7, 2], [9, 4, 1]], max_new=2, cache_len=cache_len)
        eng.generate([[8, 3, 6, 5, 2]], max_new=2, cache_len=cache_len)

    # program time on the warmed engine sets the SLO budgets and the
    # capacity estimate, so the sweep adapts to the machine
    eng = engines["fifo"]
    p0 = eng.stats.steps + eng.stats.prefill_chunks
    t0 = time.perf_counter()
    eng.generate([list(range(1, 33))], max_new=24, cache_len=cache_len)
    progs = eng.stats.steps + eng.stats.prefill_chunks - p0
    prog_s = (time.perf_counter() - t0) / max(progs, 1)

    inter = PriorityClass("interactive", priority=0, weight=0.35,
                          prompt_len=6, max_new=4,
                          slo=SLO(ttft_s=10 * prog_s))
    batch_cls = PriorityClass("batch", priority=2, weight=0.65,
                              prompt_len=32, max_new=64, slo=None)
    # programs per request: ceil(prompt/chunk) prefill + max_new+1 decode
    progs_per_req = 0.35 * (1 + 5) + 0.65 * (4 + 65)
    capacity_rps = batch / (progs_per_req * prog_s)
    base = poisson_workload(n_requests, capacity_rps, (inter, batch_cls),
                            vocab_size=cfg.vocab_size, seed=7)
    n_inter = sum(1 for w in base if w.priority == 0)
    assert 0 < n_inter < len(base), "degenerate class mix: change the seed"

    # uncontended closed-loop reference streams (parity target)
    ref_eng = BatchedOffloadEngine(model, params, None, n_total,
                                   max_batch=4)
    ref_eng.generate([[3, 5, 7]], max_new=2, cache_len=cache_len)  # warm
    rids = [ref_eng.submit(w.prompt, w.max_new, w.temperature, w.seed)
            for w in base]
    ref_res = ref_eng.run(cache_len)
    ref_streams = [ref_res[r] for r in rids]

    log(f"  slo sweep: {len(base)} requests ({n_inter} interactive), "
        f"prog {prog_s * 1e3:.1f}ms, capacity ~{capacity_rps:.2f} rps, "
        f"interactive TTFT SLO {10 * prog_s * 1e3:.0f}ms")
    log("  load_x,mode,interactive p50/p95/p99 TTFT ms,goodput rps,"
        "slo_attain,preempts")
    sweep = []
    for factor in load_factors:
        wl = scale_rate(base, factor)
        row = {"load_x": factor, "rate_rps": capacity_rps * factor}
        for name, eng in engines.items():
            pre0 = eng.stats.preemptions
            res = eng.run_workload(wl, cache_len)
            streams = [res[r] for r in sorted(res)]
            assert streams == ref_streams, (
                f"{name} streams diverged at load {factor}x")
            lat = eng.stats.latency
            pre = eng.stats.preemptions - pre0
            d = lat.as_dict()
            d["preemptions"] = pre
            # per-class views: feed record subsets back through the
            # same summariser
            recs = eng.records().values()
            inter_lat = latency_stats(
                (r for r in recs if r.priority == 0), lat.elapsed_s)
            d["interactive"] = inter_lat.as_dict()
            row[name] = d
            log(f"  {factor:.1f},{name},"
                f"{inter_lat.ttft_p50_s * 1e3:.0f}/"
                f"{inter_lat.ttft_p95_s * 1e3:.0f}/"
                f"{inter_lat.ttft_p99_s * 1e3:.0f},"
                f"{lat.goodput_rps:.2f},{lat.slo_attainment:.2f},{pre}")
        row["streams_identical"] = True
        # the comparison axis is the SLO-bearing class: preemption spends
        # best-effort batch TTFT to protect urgent TTFT, so overall p99
        # measures the wrong thing
        row["preempt_beats_fifo"] = bool(
            row["preempt"]["interactive"]["ttft_p99_s"]
            < row["fifo"]["interactive"]["ttft_p99_s"]
            and row["preempt"]["goodput_rps"] >= row["fifo"]["goodput_rps"])
        sweep.append(row)

    # the acceptance: at >=1 overload point, preemption wins on BOTH the
    # urgent class's p99 TTFT and goodput-under-SLO, and really preempted
    wins = [r for r in sweep
            if r["load_x"] > 1.0 and r["preempt_beats_fifo"]
            and r["preempt"]["preemptions"] > 0]
    assert wins, "preemption never beat FIFO at an overload point"
    log(f"  preemption wins at load {[r['load_x'] for r in wins]}x "
        "(lower p99 TTFT, no worse goodput, preemptions > 0)")
    return {
        "sweep": sweep,
        "streams_identical": True,
        "prog_s": prog_s,
        "capacity_rps_est": capacity_rps,
        "slo_ttft_s": 10 * prog_s,
        "n_requests": len(base),
        "n_interactive": n_inter,
        "win_load_x": [r["load_x"] for r in wins],
        "batch": batch,
    }


def _run_slo(n_requests, load_factors, out_path=None, log=print):
    """Build the untrained reduced backbone (scheduling + stream parity
    only — prediction quality is the policy benches' job), run the SLO
    load sweep, write the artifact."""
    import jax

    from repro.configs import get_reduced
    from repro.models import build_model

    t0 = time.time()
    cfg = get_reduced("deepseek-v2-lite")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    results = _slo_sweep(model, params, cfg, n_requests=n_requests,
                         load_factors=load_factors, log=log)
    results["wall_s"] = time.time() - t0
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        log(f"  wrote {out_path}")
    return results


def _longctx_sweep(model, params, cfg, lengths, batch: int, block_size: int,
                   iters: int, log=print):
    """Per-step decode latency vs cache length: paged flash-decode kernel
    route vs the gather-and-materialise route, same paged pools, same
    tables. ``step_s_*`` is the whole decode step (all layers + the MoE
    host loop — includes an O(cache) pool-copy both routes pay off-TPU,
    where XLA can't donate the cache buffers); ``attn_s_*`` times one paged
    attention layer's jitted program, the read path this comparison is
    about. Bytes are modeled from the cache shapes: both routes read every
    live page; only the gather route also materialises (and re-reads) the
    contiguous (N, W*block_size, ...) per-lane copy. (Off-TPU the kernel
    route is the lax.scan twin, whose live tile is capped at
    ``JNP_TILE_BLOCKS`` blocks — equal to the full copy while the table
    fits one tile, constant past it; ``materialized_bytes_kernel = 0``
    models the Pallas kernel the TPU route compiles.)"""
    import jax.numpy as jnp

    from repro.core.tracing import moe_layer_ids
    from repro.models import transformer as T
    from repro.serving.engine import DecodeCore
    from repro.serving.kvpool import blocks_for

    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    cores = {
        "kernel": DecodeCore(model, params, n_total, max_batch=batch,
                             kernel="auto"),
        "gather": DecodeCore(model, params, n_total, max_batch=batch,
                             kernel=None),
    }
    li = next(i for i, k in enumerate(cfg.layer_kinds())
              if k in T.PAGED_KINDS)
    kind = cfg.layer_kinds()[li]
    rng = np.random.default_rng(0)
    rows = []
    log(f"  longctx batch={batch} block_size={block_size}: cache_len,"
        "step_ms_kernel,step_ms_gather,attn_ms_kernel,attn_ms_gather,"
        "read_MiB,gather_materialized_MiB (kernel route materialises 0)")
    for cache_len in lengths:
        w = blocks_for(cache_len, block_size)
        num_blocks = batch * w + 1
        tables = np.stack([1 + i * w + np.arange(w) for i in range(batch)]
                          ).astype(np.int32)
        pos = [cache_len - 1] * batch
        toks = [1] * batch
        lanes = list(range(batch))
        row = {"cache_len": cache_len}
        route_caches = {}
        for name, core in cores.items():
            route_caches[name] = core.alloc_paged_caches(num_blocks,
                                                         block_size)
            block_bytes = core.paged_block_bytes(route_caches[name])
            core.step(route_caches[name], lanes, pos, toks, None, lanes,
                      tables=tables)                              # warm/jit
        # interleave routes so machine drift hits both equally
        acc = {name: 0.0 for name in cores}
        for _ in range(iters):
            for name, core in cores.items():
                t0 = time.perf_counter()
                core.step(route_caches[name], lanes, pos, toks, None, lanes,
                          tables=tables)
                acc[name] += time.perf_counter() - t0
        for name in cores:
            row[f"step_s_{name}"] = acc[name] / iters

        # isolate the read path: one paged layer's jitted attention program
        x = jnp.asarray(rng.normal(size=(batch, 1, cfg.d_model)),
                        jnp.dtype(cfg.dtype))
        tab_j = jnp.asarray(tables)
        pos_j = jnp.full((batch,), cache_len - 1, jnp.int32)
        attn_iters = 4 * iters
        for name, core in cores.items():
            lp = core.layers[li]
            cache = route_caches[name][li]
            core._paged_attn(lp, x, cache, tab_j, pos_j, kind=kind,
                             kernel=core.kernel)[0].block_until_ready()
        acc = {name: 0.0 for name in cores}
        for _ in range(attn_iters):
            for name, core in cores.items():
                lp = core.layers[li]
                cache = route_caches[name][li]
                t0 = time.perf_counter()
                core._paged_attn(lp, x, cache, tab_j, pos_j, kind=kind,
                                 kernel=core.kernel)[0].block_until_ready()
                acc[name] += time.perf_counter() - t0
        for name in cores:
            row[f"attn_s_{name}"] = acc[name] / attn_iters

        kv_read = batch * w * block_bytes
        row["kv_read_bytes"] = kv_read
        row["materialized_bytes_gather"] = kv_read
        row["materialized_bytes_kernel"] = 0
        rows.append(row)
        log(f"  {cache_len},{row['step_s_kernel'] * 1e3:.1f},"
            f"{row['step_s_gather'] * 1e3:.1f},"
            f"{row['attn_s_kernel'] * 1e3:.2f},"
            f"{row['attn_s_gather'] * 1e3:.2f},"
            f"{kv_read / 2**20:.1f},{kv_read / 2**20:.1f}")
    # growth of per-step attention-read time with cache length
    dl = max(rows[-1]["cache_len"] - rows[0]["cache_len"], 1)
    slopes = {name: (rows[-1][f"attn_s_{name}"] - rows[0][f"attn_s_{name}"])
              / dl for name in cores}
    log(f"  attn-read growth: kernel {slopes['kernel'] * 1e6:.3f}us/pos, "
        f"gather {slopes['gather'] * 1e6:.3f}us/pos "
        f"({slopes['gather'] / max(slopes['kernel'], 1e-12):.2f}x)")
    return {"rows": rows, "slope_s_per_pos_kernel": slopes["kernel"],
            "slope_s_per_pos_gather": slopes["gather"],
            "kernel_routes": {n: c.kernel for n, c in cores.items()},
            "batch": batch, "block_size": block_size}


def _run_tiers(out_path=None, replacement="both", cold_dtype="both",
               dispatch="fetch", log=print):
    """Build the untrained reduced backbone (stream parity + modeled stall
    only — prediction quality is the policy benches' job), run the tier
    sweep, write the artifact."""
    import jax

    from repro.configs import get_reduced
    from repro.data import make_topic_corpus, sample_prompts
    from repro.models import build_model

    t0 = time.time()
    cfg = get_reduced("deepseek-v2-lite")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    corpus = make_topic_corpus(cfg.vocab_size, n_topics=4, seed=0)
    prompts = sample_prompts(corpus, 6, 8, seed=2)
    results = _tier_sweep(model, params, cfg, prompts, max_new=6,
                          cache_len=32, batch=4, replacement=replacement,
                          cold_dtype=cold_dtype, dispatch=dispatch,
                          log=log)
    results["wall_s"] = time.time() - t0
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        log(f"  wrote {out_path}")
    return results


def _run_trace(out_path=None, log=print):
    """Telemetry-trace mode: the tiered paged engine with the runtime
    telemetry layer on (``src/repro/serving/telemetry.py``), pinned
    against a telemetry-off twin, writing a Chrome-trace artifact.

    Three runs of the same shared-prefix workload through the paged
    engine with a 4-shard tiered expert store (so at least two copy
    channels carry traffic): a single-host token-stream reference, a
    telemetry-off tiered run, and a telemetry-on tiered run. Asserts the
    zero-overhead contract — telemetry on/off produce token-identical
    streams and identical deterministic engine stats (everything except
    the wall-clock ``latency`` summary) — then writes the on-run's
    ``Telemetry.to_chrome_trace()`` JSON with the predictor
    ``scoreboard`` section riding in the same file (Perfetto ignores
    unknown top-level keys). ``tools/check_trace.py`` validates the
    artifact in CI."""
    import jax

    from repro.configs import get_reduced
    from repro.core.policies import NextLayerAllPolicy
    from repro.core.tracing import moe_layer_ids
    from repro.launch.dryrun import decode_layer_roofline
    from repro.data import make_topic_corpus
    from repro.models import build_model
    from repro.serving.config import ServeConfig
    from repro.serving.expertstore import TierConfig
    from repro.serving.scheduler import BatchedOffloadEngine
    from repro.serving.telemetry import Telemetry

    t0 = time.time()
    cfg = get_reduced("deepseek-v2-lite")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    corpus = make_topic_corpus(cfg.vocab_size, n_topics=4, seed=0)
    # shared 16-token system prefix -> the prefix cache has adoptions
    prompts = _prefix_workload(cfg, corpus, n_requests=6, sys_len=16,
                               tail_len=6, seed=7)

    n_moe = len(moe_layer_ids(cfg))
    e = cfg.moe.num_experts
    n_total = n_moe * e
    batch, max_new, cache_len = 4, 6, 48
    pol = NextLayerAllPolicy(e)
    cap = max(batch * cfg.moe.top_k, n_total // 3)

    def build(tel, tiers=None, host_bw=100e9):
        serve = ServeConfig(max_batch=batch, block_size=8,
                            prefix_cache=True,
                            layer_compute_s="roofline" if tiers else 0.0,
                            tiers=tiers, telemetry=tel)
        return BatchedOffloadEngine(model, params, pol, cap,
                                    host_bw=host_bw, serve=serve)

    # single-host reference: the tiered runs must not change a token
    ref = build(None)
    ref_out = ref.generate(prompts, max_new=max_new, cache_len=cache_len)
    expert_bytes = ref.core.store.bytes_per_expert

    # tier hardware model scaled to this arch's roofline, as in the
    # --tiers sweep: slow-tier fetches cost layers of compute, so the
    # channel tracks carry visible transfer spans
    per_layer = decode_layer_roofline(cfg, batch=batch)
    mean_layer = sum(a + f for a, f in per_layer) / len(per_layer)
    shards = 4
    dram = max(1, n_total // (shards * 4))
    disk_per_layer = max(1, (n_total - shards * dram) // n_moe)
    peer_per_layer = max(1, (shards - 1) * dram // n_moe)
    dur_disk = 2.2 * mean_layer / disk_per_layer
    dur_peer = 1.5 * mean_layer / peer_per_layer
    tc = TierConfig(num_shards=shards, shard_dram_experts=dram,
                    cache_experts=max(2, n_total // 6),
                    peer_latency_s=0.3 * dur_peer,
                    peer_bw=expert_bytes / (0.7 * dur_peer),
                    disk_latency_s=0.3 * dur_disk,
                    disk_bw=expert_bytes / (0.7 * dur_disk),
                    horizons=(1, 1, 2, 3))
    host_bw = expert_bytes * e / (0.4 * mean_layer)

    def det_stats(eng):
        d = eng.stats.as_dict()
        d.pop("latency")          # wall-clock, legitimately differs
        return d

    off = build(None, tiers=tc, host_bw=host_bw)
    off_out = off.generate(prompts, max_new=max_new, cache_len=cache_len)
    off.core.store.close()

    tel = Telemetry()
    on = build(tel, tiers=tc, host_bw=host_bw)
    on_out = on.generate(prompts, max_new=max_new, cache_len=cache_len)
    on.core.store.close()

    assert on_out == off_out == ref_out, \
        "telemetry (or the tiered store) changed a token stream"
    assert det_stats(on) == det_stats(off), \
        "telemetry changed the engine's deterministic stats"

    trace = tel.to_chrome_trace()
    trace["scoreboard"] = tel.scoreboard(bucket_s=0.25)
    trace["wall_s"] = time.time() - t0

    evs = trace["traceEvents"]
    req_tracks = sum(1 for ev in evs if ev.get("ph") == "M"
                     and ev.get("name") == "thread_name"
                     and ev.get("pid") == 1)
    chan_tracks = sum(1 for ev in evs if ev.get("ph") == "M"
                      and ev.get("name") == "thread_name"
                      and ev.get("pid") == 2)
    total = trace["scoreboard"]["total"]
    log(f"  trace: {len(evs)} events, {req_tracks} request tracks, "
        f"{chan_tracks} channel tracks, "
        f"{len(trace['scoreboard']['windows'])} scoreboard windows")
    log(f"  predictor: precision={total['precision']:.3f} "
        f"recall={total['recall']:.3f} f1={total['f1']:.3f} "
        f"tier01_hit_rate={total['t01_hit_rate']:.3f}")
    log("  on/off parity: token streams identical, deterministic stats "
        "identical")
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(trace, f, indent=2)
        log(f"  wrote {out_path} (open in ui.perfetto.dev)")
    return trace


def _run_longctx(lengths, iters, out_path=None, log=print):
    """Build the untrained reduced backbone (attention timing only — parity
    is the tests' job), run the sweep, write the artifact."""
    import jax

    from repro.configs import get_reduced
    from repro.models import build_model

    t0 = time.time()
    cfg = get_reduced("deepseek-v2-lite")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    results = _longctx_sweep(model, params, cfg, lengths=lengths, batch=4,
                             block_size=16, iters=iters, log=log)
    results["wall_s"] = time.time() - t0
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        log(f"  wrote {out_path}")
    return results


def run(log=print):
    from benchmarks.common import trained_predictor
    from repro.core.policies import (MoEInfinityPolicy, NextLayerAllPolicy,
                                     NoPrefetchPolicy, OnlineMoEBeyondPolicy)
    from repro.core.tracing import moe_layer_ids
    from repro.data import make_topic_corpus, sample_prompts
    from repro.serving.engine import OffloadEngine

    pcfg, pp, hist, bundle = trained_predictor(log=log)
    cfg, model, params, train_traces, _ = bundle
    n_moe = len(moe_layer_ids(cfg))
    e = cfg.moe.num_experts
    capacity = max(1, int(0.2 * n_moe * e))

    corpus = make_topic_corpus(cfg.vocab_size, n_topics=8, seed=3)
    prompt = sample_prompts(corpus, 1, 12, seed=5)[0]

    policies = {
        "none": NoPrefetchPolicy(),
        "next-layer-all": NextLayerAllPolicy(e),
        "moe-infinity": MoEInfinityPolicy(train_traces, n_moe, e, width=6),
        "moe-beyond-online": OnlineMoEBeyondPolicy(pp, pcfg, width=6),
    }
    out = {}
    log("  policy,cache_hit,fetch_MiB,stall_ms,blocking_ms "
        "(engine, capacity 20%, layer_compute 50us)")
    for name, pol in policies.items():
        eng = OffloadEngine(model, params, pol, capacity,
                            layer_compute_s=50e-6)
        eng.generate(prompt, max_new=36, cache_len=64)
        s = eng.stats
        log(f"  {name},{s.hit_rate:.3f},{s.fetch_bytes / 2**20:.1f},"
            f"{s.sim_stall_s * 1e3:.1f},{s.blocking_stall_s * 1e3:.1f}")
        out[f"engine_{name}_hit"] = s.hit_rate
        out[f"engine_{name}_stall_ms"] = s.sim_stall_s * 1e3

    prompts = sample_prompts(corpus, 4, 12, seed=6)
    tp = _throughput(model, params, cfg, prompts, max_new=24, cache_len=64,
                     batch=4, log=log)
    out.update({f"batched_{k}": v for k, v in tp.items()})
    return out


def run_tiny(out_path=None, mixed=False, longctx=False, prefix=False,
             tiers=False, slo=False, trace=False, replacement="both",
             cold_dtype="both", dispatch="fetch", sanitize=False,
             log=print):
    """CI smoke: briefly-trained reduced backbone, no cached artifacts;
    writes the JSON artifact the workflow uploads. ``mixed`` switches to the
    ragged-length admission-latency / memory-high-water workload;
    ``longctx`` to the cache-length sweep (kernel vs gather read path —
    untrained weights, attention timing only); ``prefix`` to the
    shared-system-prompt workload (prefix cache on vs off); ``tiers`` to
    the tiered expert-store sweep (untrained weights — stream parity and
    modeled stall); ``slo`` to the open-loop SLO load sweep (untrained
    weights — preemptive vs FIFO scheduling under Poisson traffic);
    ``trace`` to the telemetry-trace mode (untrained weights — Chrome
    trace + predictor scoreboard artifact, telemetry on/off parity
    asserted); ``sanitize`` wraps any of the above in the retrace/leak
    sanitizer layer and adds a ``"sanitizer"`` section to the
    artifact."""
    from repro.configs import get_reduced
    from repro.core.policies import NextLayerAllPolicy, NoPrefetchPolicy
    from repro.core.tracing import moe_layer_ids
    from repro.data import make_topic_corpus, sample_prompts
    from repro.launch.train import train
    from repro.models import build_model
    from repro.serving.engine import OffloadEngine

    if sanitize:
        with _SanitizerSession() as ses:
            results = run_tiny(out_path=None, mixed=mixed, longctx=longctx,
                               prefix=prefix, tiers=tiers, slo=slo,
                               trace=trace, replacement=replacement,
                               cold_dtype=cold_dtype, dispatch=dispatch,
                               sanitize=False, log=log)
        # zero observed compile events would mean the hook is dead and the
        # compile counts vacuous — fail the bench rather than report them
        ses.guard.self_check()
        results["sanitizer"] = ses.report()
        log(f"  sanitizer: {json.dumps(results['sanitizer'], indent=2)}")
        if out_path:
            os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                        exist_ok=True)
            with open(out_path, "w") as f:
                json.dump(results, f, indent=2)
            log(f"  wrote {out_path}")
        return results

    t0 = time.time()
    arch = "deepseek-v2-lite"
    if longctx:
        return _run_longctx(lengths=(1024, 2048, 4096, 8192), iters=5,
                            out_path=out_path, log=log)
    if tiers:
        return _run_tiers(out_path=out_path, replacement=replacement,
                          cold_dtype=cold_dtype, dispatch=dispatch,
                          log=log)
    if slo:
        return _run_slo(n_requests=16, load_factors=(0.4, 1.5, 4.0),
                        out_path=out_path, log=log)
    if trace:
        return _run_trace(out_path=out_path, log=log)
    params, _ = train(arch, reduced=True, steps=30, batch_size=8,
                      seq_len=64, lr=3e-3, log=log)
    cfg = get_reduced(arch)
    model = build_model(cfg)
    corpus = make_topic_corpus(cfg.vocab_size, n_topics=4, seed=0)
    n_moe = len(moe_layer_ids(cfg))
    e = cfg.moe.num_experts

    if mixed or prefix:
        if prefix:
            sys_len = 64
            prompts = _prefix_workload(cfg, corpus, n_requests=8,
                                       sys_len=sys_len, tail_len=8, seed=13)
            results = _prefix_sharing(model, params, cfg, prompts,
                                      shared_len=sys_len, max_new=8,
                                      cache_len=96, batch=4, block_size=8,
                                      log=log)
        else:
            prompts = _mixed_workload(cfg, corpus, n_requests=8, seed=11)
            results = _mixed_latency(model, params, cfg, prompts, max_new=8,
                                     cache_len=48, batch=4, log=log)
        results["wall_s"] = time.time() - t0
        mode = "prefix" if prefix else "mixed"
        log(f"  tiny {mode} bench: {json.dumps(results, indent=2)}")
        if out_path:
            os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                        exist_ok=True)
            with open(out_path, "w") as f:
                json.dump(results, f, indent=2)
            log(f"  wrote {out_path}")
        return results

    prompts = sample_prompts(corpus, 4, 8, seed=1)
    results = _throughput(model, params, cfg, prompts, max_new=12,
                          cache_len=32, batch=4, log=log)

    cap = max(model.cfg.moe.top_k * 4 + 1, (n_moe * e) // 4)
    eng = OffloadEngine(model, params, NoPrefetchPolicy(), cap,
                        layer_compute_s=50e-6)
    eng.generate(prompts[0], max_new=12, cache_len=32)
    s = eng.stats
    # prefetch-ahead engine: transfers hide behind modeled compute
    pre = OffloadEngine(model, params, NextLayerAllPolicy(e), cap,
                        layer_compute_s=50e-6)
    pre.generate(prompts[0], max_new=12, cache_len=32)
    results.update({
        "hit_rate_small_cache": s.hit_rate,
        "stall_ms": s.sim_stall_s * 1e3,
        "blocking_stall_ms": s.blocking_stall_s * 1e3,
        "prefetch_hit_rate": pre.stats.hit_rate,
        "prefetch_stall_ms": pre.stats.sim_stall_s * 1e3,
        "prefetch_blocking_stall_ms": pre.stats.blocking_stall_s * 1e3,
        "prefetch_overlapped_ms": pre.stats.overlapped_s * 1e3,
        "wall_s": time.time() - t0,
    })
    log(f"  tiny bench: {json.dumps(results, indent=2)}")
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(results, f, indent=2)
        log(f"  wrote {out_path}")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: tiny backbone, no cached artifacts")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--mixed", action="store_true",
                      help="mixed-length workload: admission-to-first-token "
                           "latency + KV memory high-water, paged vs token "
                           "prompt path")
    mode.add_argument("--longctx", action="store_true",
                      help="cache-length sweep: per-step decode latency + "
                           "bytes read, paged flash-decode kernel vs gather")
    mode.add_argument("--prefix", action="store_true",
                      help="shared-system-prompt workload: prefix cache on "
                           "vs off — hit rate, skipped prefill, TTFT, KV "
                           "high-water")
    mode.add_argument("--tiers", action="store_true",
                      help="tiered expert store: shard count x tier-0 "
                           "capacity sweep (per-tier hit rates, "
                           "stall-by-tier, tok/s) + horizon-aware vs "
                           "fixed-horizon prefetch")
    mode.add_argument("--slo", action="store_true",
                      help="open-loop Poisson load sweep: preemptive vs "
                           "FIFO scheduling — p50/p95/p99 TTFT, "
                           "goodput-under-SLO, preemption counts, with "
                           "streams pinned to an uncontended reference")
    mode.add_argument("--trace", action="store_true",
                      help="telemetry trace: tiered paged engine with the "
                           "runtime telemetry layer on — Chrome-trace "
                           "artifact (open in ui.perfetto.dev) with the "
                           "predictor scoreboard, on/off parity asserted")
    ap.add_argument("--replacement", choices=("lru", "learned", "both"),
                    default="both",
                    help="--tiers only: eviction policies to sweep "
                         "(learned = predictor-driven reuse-distance "
                         "replacement)")
    ap.add_argument("--cold-dtype", choices=("none", "int8", "both"),
                    default="both",
                    help="--tiers only: cold-tier (peer/disk) storage "
                         "dtype comparison; int8 halves fetch bytes but "
                         "is lossy")
    ap.add_argument("--dispatch", choices=("fetch", "ship", "auto", "all"),
                    default="fetch",
                    help="--tiers only: compute-dispatch modes to compare "
                         "in a cold-expert regime (ship = send the token "
                         "group to the expert's shard instead of pulling "
                         "its weights; auto = roofline-priced per "
                         "(expert, token-count))")
    ap.add_argument("--sanitize", action="store_true",
                    help="tiny modes: wrap the run in the retrace/leak "
                         "sanitizer layer — per-program XLA compile counts "
                         "in the artifact plus a pool/residency ledger "
                         "check at every request retire")
    ap.add_argument("--out", default=None, help="JSON artifact path")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.longctx and not args.tiny:
        _run_longctx(lengths=(1024, 4096, 8192, 16384, 32768), iters=3,
                     out_path=args.out)
    elif args.slo and not args.tiny:
        _run_slo(n_requests=40, load_factors=(0.4, 1.0, 1.5, 2.5, 4.0),
                 out_path=args.out)
    elif (args.tiny or args.mixed or args.prefix or args.tiers or args.slo
          or args.trace):
        run_tiny(args.out, mixed=args.mixed, longctx=args.longctx,
                 prefix=args.prefix, tiers=args.tiers, slo=args.slo,
                 trace=args.trace, replacement=args.replacement,
                 cold_dtype=args.cold_dtype, dispatch=args.dispatch,
                 sanitize=args.sanitize)
    else:
        results = run()
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=2)


if __name__ == "__main__":
    main()

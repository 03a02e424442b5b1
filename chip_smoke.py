"""Smoke run of the offload serving path on one TPU, at DeepSeek-V2-Lite's
published widths, with random weights drawn from a seed.

    python chip_smoke.py

It drives ``BatchedOffloadEngine`` with the default ``ServeConfig`` (paged
MLA KV pool, chunked prefill, the paged flash-decode kernel compiled for the
TPU) and the host expert store: routed experts live in host memory, and the
device holds them only in the tier-0 slot buffer.

1. Correctness, at a depth one chip holds whole (the dense layer and two
   MoE layers, every expert resident): the engine's prefill-then-decode
   logits against the model's own prefill and decode step, with the
   model's float32 run as the yardstick for bfloat16 rounding.
2. Serving: all 27 layers, unless host memory cannot hold their experts
   or the expert fills (timed in this run) would not finish within the
   run's 1200 s limit; then the dense layer and as many MoE layers as fit,
   and the cut is printed. 10% of the experts in tier 0, the paper's
   predictor choosing prefetches, 8 requests of 64-256 prompt tokens and
   32 new tokens each.

Every number printed is a reading of this one run, not a benchmark figure.
Any failed check exits non-zero before the last line; without a TPU the
script stops before doing anything else. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

SEED = 0
N_REQUESTS = 8
PROMPT_LEN = (64, 256)              # inclusive range of prompt lengths
MAX_NEW = 32
CAPACITY_FRAC = 0.10                # tier-0 slots, share of all experts
# host memory kept free beyond the routed experts: the f32 embedding copy
# the policies read, the runtime, and one layer in flight during init
HOST_HEADROOM = 8 << 30

REF_MOE_LAYERS = 2
REF_PROMPT_LENS = (64, 129)         # 129: a chunk remainder of one token
REF_NEW = 8
# The engine and the model both compute in bfloat16, as the configuration
# states, in different orders (chunked absorbed-latent attention through
# the kernel vs the materialised prefill; slot gather vs one-hot dispatch),
# so neither is exact. The yardstick is the model's own prefill and decode
# step in float32 on the same weights: the engine's logits may be at most
# this many times further from it (relative L2, worst position) than the
# model's own bfloat16 logits are. Both errors are 1-3.5% per position at
# three layers (CPU runs of this check at widths 128 and 512 put the ratio
# of the worst errors at 0.7 and 1.1); a wrong expert, page or position
# moves logits by tens of percent.
ERROR_RATIO = 2.0
# A rounding difference can reorder two near-equal router probabilities and
# swap the 6th expert for the 7th; that position's logits then differ by
# more than rounding. Positions where the engine and the two model runs do
# not all route alike are counted, not bounded, and may be at most this
# share of those compared.
MAX_ROUTING_FLIP_SHARE = 0.25

# The whole run must end within this limit. The serving phase is bound by
# expert fills (one 17.3 MB host->device copy per tier-0 miss or prefetch),
# so its depth is also cut to what the fill time measured in this run lets
# it finish. Fills per token per MoE layer is a count, not a time: 4.2 for
# this traffic in a CPU run at 64 experts, top-6, 10% of 26 layers' experts
# in tier 0 (the count does not depend on widths); 5 leaves a margin.
RUN_LIMIT_S = 1200
FILLS_PER_TOKEN_LAYER = 5.0
# the serving phase's own set-up: weight init, compiles the reference phase
# did not share, and the final checks
SERVE_SETUP_S = 240


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def reading(label: str, value) -> None:
    print(f"smoke reading (one run, not a benchmark): {label}: {value}")


def tpu_device():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r}; this smoke "
             "run measures the serving path on a TPU and never falls back")
    return dev


def host_memory():
    """(total, available) host bytes: /proc/meminfo, bounded by a cgroup
    limit when one is set."""
    info = {}
    with open("/proc/meminfo") as f:
        for line in f:
            key, val = line.split(":", 1)
            info[key] = int(val.split()[0]) * 1024
    total, avail = info["MemTotal"], info["MemAvailable"]
    try:
        with open("/sys/fs/cgroup/memory.max") as f:
            limit = f.read().strip()
        if limit != "max":
            with open("/sys/fs/cgroup/memory.current") as f:
                used = int(f.read())
            total = min(total, int(limit))
            avail = min(avail, int(limit) - used)
    except OSError:
        pass
    return total, avail


class StepRecorder:
    """Stands in for ``DecodeCore.step`` and keeps what each decode step
    returned: its wall time (the step returns host logits, so the device
    has finished), whether its logits were finite, and optionally the
    logits and routed experts per (request, position)."""

    def __init__(self, core, keep: bool):
        self._step = core.step
        core.step = self
        self.keep = keep
        self.walls = []
        self.finite = True
        self.rows = {}

    def __call__(self, *args, **kw):
        t0 = time.perf_counter()
        logits, caches, experts = self._step(*args, **kw)
        self.walls.append(time.perf_counter() - t0)
        self.finite &= bool(np.isfinite(logits).all())
        if self.keep:
            for rid, pos, lg, ex in zip(kw["rids"], kw["pos"], logits,
                                        experts):
                self.rows[(rid, pos)] = (lg, [set(map(int, e)) for e in ex])
        return logits, caches, experts


def check_routed_placement(jax, engine, cfg) -> None:
    """Routed experts are on the device only in the tier-0 slot buffer."""
    from repro.serving.engine import ROUTED
    core = engine.core
    for li, lp in enumerate(core.layers):
        check(not set(ROUTED) & set(lp.get("moe", {})),
              f"layer {li}'s device params hold routed expert stacks")
    e, d, f = cfg.moe.num_experts, cfg.d_model, cfg.moe.d_ff_expert
    slots = {id(core.slots.w_gate), id(core.slots.w_up),
             id(core.slots.w_down)}
    stray = [a.shape for a in jax.live_arrays()
             if id(a) not in slots and a.shape[-3:] in ((e, d, f), (e, f, d))]
    check(not stray, f"routed expert stacks live on the device: {stray}")


def check_paged_kernel(jax, engine) -> None:
    """The paged attention step compiles to the TPU kernel."""
    import jax.numpy as jnp
    core = engine.core
    check(core.kernel == "tpu",
          f"paged attention resolved to {core.kernel!r}, not 'tpu'")
    li = next(i for i, k in enumerate(core.kinds) if k == "mla")
    cache = core.alloc_paged_caches(2, engine.block_size)[li]
    n = engine.max_batch
    x = jnp.zeros((n, 1, core.cfg.d_model), jnp.dtype(core.cfg.dtype))
    tables = jnp.zeros((n, 1), jnp.int32)
    pos = jnp.zeros((n,), jnp.int32)
    text = core._paged_attn.lower(core.layers[li], x, cache, tables, pos,
                                  kind="mla", kernel=core.kernel).as_text()
    check("tpu_custom_call" in text,
          "the lowered paged decode step holds no tpu_custom_call")


def model_rows(jax, cfg, params, prompts, streams, cache_len) -> dict:
    """The model's own prefill of each prompt, then one decode step per
    token the engine fed back, at ``cfg.dtype``: (logits, routed experts
    per MoE layer) per (request, position)."""
    import jax.numpy as jnp

    from repro.core.tracing import extract_step_experts
    from repro.models import build_model
    from repro.models import transformer as T

    dt = jnp.dtype(cfg.dtype)
    dev_params = jax.tree.map(lambda x: jnp.asarray(x, dt), params)
    prefill = jax.jit(build_model(cfg).prefill, static_argnums=2)
    decode = jax.jit(lambda p, c, pos, tok: T.lm_apply(
        p, cfg, tok, None, mode="decode", caches=c, pos=pos))
    rows = {}
    for rid, prompt in prompts.items():
        _, state = prefill(
            dev_params, {"tokens": jnp.asarray([prompt[:-1]], jnp.int32)},
            cache_len)
        caches, pos = state["caches"], len(prompt) - 1
        for tok in [prompt[-1]] + streams[rid][:-1]:
            logits, caches, extras, _ = decode(
                dev_params, caches, pos, jnp.full((1, 1), tok, jnp.int32))
            rows[(rid, pos)] = (
                np.asarray(logits, np.float32)[0, -1],
                [set(map(int, e)) for e in extract_step_experts(cfg, extras)])
            pos += 1
    return rows


def reference_phase(jax, base_cfg) -> float:
    """Checks the engine's logits against the model's own prefill and
    decode step; returns the measured seconds per expert fill."""
    from repro.models import build_model
    from repro.serving.config import ServeConfig
    from repro.serving.engine import init_offload_params
    from repro.serving.scheduler import BatchedOffloadEngine

    m = base_cfg.moe
    # the model's prefill dispatches through a one-hot capacity buffer that
    # drops tokens past an expert's capacity; the engine never drops one.
    # capacity_factor = E / k sizes the buffer for every token.
    cfg = base_cfg.replace(
        num_layers=m.first_dense_layers + REF_MOE_LAYERS,
        moe=dataclasses.replace(m, capacity_factor=m.num_experts / m.top_k))
    params = init_offload_params(cfg, jax.random.PRNGKey(SEED))
    engine = BatchedOffloadEngine(build_model(cfg), params, None,
                                  REF_MOE_LAYERS * m.num_experts,
                                  serve=ServeConfig())
    check_paged_kernel(jax, engine)
    check_routed_placement(jax, engine, cfg)
    rec = StepRecorder(engine.core, keep=True)
    rng = np.random.default_rng(SEED + 1)
    prompts = {engine.submit(p, REF_NEW): p
               for p in (rng.integers(0, cfg.vocab_size, n).tolist()
                         for n in REF_PROMPT_LENS)}
    cache_len = max(REF_PROMPT_LENS) + REF_NEW + 1
    streams = engine.run(cache_len)
    fill_s = time_fills(jax, engine.core.store)
    del engine
    gc.collect()

    ref = model_rows(jax, cfg, params, prompts, streams, cache_len)
    with jax.default_matmul_precision("float32"):
        exact = model_rows(jax, cfg.replace(dtype="float32"), params,
                           prompts, streams, cache_len)
    del params
    gc.collect()

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    errs, floors, flips = [], [], 0
    for key, (got, got_experts) in rec.rows.items():
        check(np.isfinite(got).all(), f"non-finite logits at {key}")
        (lg16, ex16), (lg32, ex32) = ref[key], exact[key]
        if not got_experts == ex16 == ex32:
            flips += 1
            continue
        errs.append(rel(got, lg32))
        floors.append(rel(lg16, lg32))
    n = len(rec.rows)
    check(n == len(ref), f"{n} engine decode positions, {len(ref)} expected")
    reading("reference check positions compared", n)
    reading("reference check routing flips (excluded)", flips)
    check(flips <= MAX_ROUTING_FLIP_SHARE * n,
          f"routing differs from the model's at {flips} of {n} positions")
    reading("reference check max relative L2 logits error vs float32, "
            "engine", max(errs))
    reading("reference check max relative L2 logits error vs float32, "
            "model in bfloat16", max(floors))
    check(max(errs) <= ERROR_RATIO * max(floors),
          f"engine logits are {max(errs):.4g} (relative L2) from the "
          f"float32 model, more than {ERROR_RATIO} x the bfloat16 model's "
          f"{max(floors):.4g}")
    return fill_s


def time_fills(jax, store) -> float:
    """Seconds per expert fill through the engine's slot-buffer path (host
    store read, host->device copy, in-place slot write), waited for: half
    of one layer's experts."""
    from repro.serving.offload import SlotBuffer
    n = store.num_experts // 2
    buf = SlotBuffer(store, n + 1)
    buf.fill((0, n))                            # compiles the slot write
    jax.block_until_ready(buf.w_down)
    t0 = time.perf_counter()
    for e in range(n):
        buf.fill((0, e))
    jax.block_until_ready((buf.w_gate, buf.w_up, buf.w_down))
    return (time.perf_counter() - t0) / n


def serve_prompt_lens() -> np.ndarray:
    return np.random.default_rng(SEED + 3).integers(
        PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_REQUESTS)


def serve_phase(jax, dev, base_cfg, n_moe: int) -> None:
    from repro.configs.base import PredictorConfig
    from repro.core.policies import OnlineMoEBeyondPolicy
    from repro.core.predictor import predictor_init
    from repro.models import build_model
    from repro.serving.config import ServeConfig
    from repro.serving.engine import init_offload_params
    from repro.serving.scheduler import BatchedOffloadEngine

    m = base_cfg.moe
    cfg = base_cfg.replace(num_layers=m.first_dense_layers + n_moe)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = init_offload_params(cfg, jax.random.PRNGKey(SEED))
    reading("weight init wall s (host experts, one layer at a time)",
            time.perf_counter() - t0)
    # the paper's predictor; its input and output widths are the backbone's
    # (DeepSeek-V2-Lite's are the defaults)
    pcfg = PredictorConfig().replace(token_emb_dim=cfg.d_model,
                                     num_experts=m.num_experts)
    predictor = predictor_init(jax.random.PRNGKey(SEED + 2), pcfg)
    serve = ServeConfig()
    capacity = max(serve.max_batch * m.top_k,
                   round(CAPACITY_FRAC * n_moe * m.num_experts))
    engine = BatchedOffloadEngine(
        model, params, lambda: OnlineMoEBeyondPolicy(predictor, pcfg),
        capacity, serve=serve)
    del params
    gc.collect()
    check_paged_kernel(jax, engine)
    check_routed_placement(jax, engine, cfg)
    reading("tier-0 slots", f"{capacity} of {n_moe * m.num_experts} experts")

    lens = serve_prompt_lens()
    rng = np.random.default_rng(SEED + 4)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]
    rec = StepRecorder(engine.core, keep=False)
    rids = [engine.submit(p, MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    results = engine.run(int(lens.max()) + MAX_NEW + 1)
    wall = time.perf_counter() - t0

    check(sorted(results) == sorted(rids),
          f"{len(results)} of {len(rids)} requests answered")
    # every engine yields max_new + 1 tokens per request: the last prompt
    # token's logits give the first, then max_new decode steps follow
    bad = {r: len(results[r]) for r in rids if len(results[r]) != MAX_NEW + 1}
    check(not bad, f"token counts off (expected {MAX_NEW + 1}): {bad}")
    check(rec.finite, "non-finite logits in the serving run")
    s = engine.stats
    walls = np.asarray(rec.walls)
    reading("requests answered", len(results))
    reading("prompt tokens", int(lens.sum()))
    reading("serving run wall s (compiles included)", wall)
    reading("decode steps", len(walls))
    reading("decode step wall s, median (host logits in hand)",
            float(np.median(walls)))
    reading("decode step wall s, max", float(walls.max()))
    reading("prefill chunks", s.prefill_chunks)
    reading("tier-0 hit rate", s.hit_rate)
    reading("expert fills", engine.core.slots.fetch_count)
    reading("expert bytes fetched host->device", s.fetch_bytes)
    reading("peak device bytes in use",
            (dev.memory_stats() or {}).get("peak_bytes_in_use"))


def main() -> None:
    t_start = time.perf_counter()
    dev = tpu_device()
    import jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs.deepseek_v2_lite import CONFIG
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    print(f"device_kind: {dev.device_kind} (platform {dev.platform}, "
          f"{len(jax.devices())} device(s))")
    print(f"compile cache: {cache_dir}")
    total, avail = host_memory()
    m = CONFIG.moe
    layer_bytes = (3 * m.num_experts * CONFIG.d_model * m.d_ff_expert
                   * np.dtype(CONFIG.dtype).itemsize)
    n_moe_all = CONFIG.num_layers - m.first_dense_layers
    n_mem = int(min(n_moe_all, (avail - HOST_HEADROOM) // layer_bytes))
    print(f"host RAM: {total / 2**30:.1f} GiB total, "
          f"{avail / 2**30:.1f} GiB available; routed experts need "
          f"{n_moe_all * layer_bytes / 2**30:.1f} GiB for all "
          f"{n_moe_all} MoE layers")
    check(n_mem >= 1, "host memory holds no MoE layer's experts")
    if n_mem < n_moe_all:
        print(f"depth cut: {n_mem} of {n_moe_all} MoE layers, because host "
              "memory holds no more of their experts (widths unchanged)")

    fill_s = reference_phase(jax, CONFIG)
    reading("start-up and reference phase wall s",
            time.perf_counter() - t_start)
    reading("expert fill wall s (one expert host->device, waited for)",
            fill_s)
    lens = serve_prompt_lens()
    fills_per_layer = (FILLS_PER_TOKEN_LAYER
                       * (int(lens.sum()) + N_REQUESTS * MAX_NEW))
    left = RUN_LIMIT_S - SERVE_SETUP_S - (time.perf_counter() - t_start)
    n_time = int(left // (fills_per_layer * fill_s))
    n_moe = max(1, min(n_mem, n_time))
    if n_time < n_mem:
        print(f"depth cut: {n_moe} of {n_moe_all} MoE layers, because about "
              f"{fills_per_layer:.0f} expert fills per MoE layer at "
              f"{fill_s * 1e3:.2f} ms each must finish in the {left:.0f} s "
              f"left of the {RUN_LIMIT_S} s limit (widths unchanged)")
    print(f"depth used: {m.first_dense_layers + n_moe} layers "
          f"({m.first_dense_layers} dense + {n_moe} MoE) of "
          f"{CONFIG.num_layers}; every width as published", flush=True)
    t0 = time.perf_counter()
    serve_phase(jax, dev, CONFIG, n_moe)
    reading("serving phase wall s", time.perf_counter() - t0)
    reading("XLA compile s, summed over programs (cache hits excluded)",
            compile_s[0])
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

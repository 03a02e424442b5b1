"""Serving launcher: batch-1 offloaded decode with a chosen prefetch policy.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-v2-lite \
      --capacity-frac 0.2 --policy moe-infinity --tokens 64
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_reduced
from repro.core.policies import (GlobalFrequencyPolicy, MoEInfinityPolicy,
                                 NextLayerAllPolicy, NoPrefetchPolicy,
                                 OnlineMoEBeyondPolicy, RandomPolicy)
from repro.core.tracing import collect_traces, moe_layer_ids
from repro.data import make_topic_corpus, sample_prompts
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import train
from repro.models import build_model
from repro.serving.engine import OffloadEngine


def build_policy(name: str, cfg, train_traces, width: int = 6,
                 predictor=None, pcfg=None):
    n_layers = len(moe_layer_ids(cfg))
    e = cfg.moe.num_experts
    if name == "none":
        return NoPrefetchPolicy()
    if name == "random":
        return RandomPolicy(e, width)
    if name == "next-layer-all":
        return NextLayerAllPolicy(e)
    if name == "global-frequency":
        return GlobalFrequencyPolicy(train_traces, n_layers, e, width)
    if name == "moe-infinity":
        return MoEInfinityPolicy(train_traces, n_layers, e, width)
    if name == "moe-beyond":
        assert predictor is not None
        return OnlineMoEBeyondPolicy(predictor, pcfg, width)
    raise ValueError(name)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-v2-lite")
    ap.add_argument("--policy", default="moe-infinity")
    ap.add_argument("--capacity-frac", type=float, default=0.2)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--train-steps", type=int, default=60)
    ap.add_argument("--n-train-traces", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    params, _ = train(args.arch, reduced=True, steps=args.train_steps,
                      batch_size=16, seq_len=64)
    cfg = get_reduced(args.arch)
    model = build_model(cfg)
    corpus = make_topic_corpus(cfg.vocab_size, n_topics=8, seed=0)

    train_traces = collect_traces(
        model, params, sample_prompts(corpus, args.n_train_traces, 16),
        max_new=48, cache_len=80)

    n_layers = len(moe_layer_ids(cfg))
    capacity = max(1, int(args.capacity_frac * n_layers
                          * cfg.moe.num_experts))
    policy = build_policy(args.policy, cfg, train_traces)
    engine = OffloadEngine(model, params, policy, capacity)

    prompt = sample_prompts(corpus, 1, 16, seed=123)[0]
    t0 = time.time()
    out = engine.generate(prompt, max_new=args.tokens,
                          cache_len=len(prompt) + args.tokens + 1)
    dt = time.time() - t0
    s = engine.stats
    print(f"policy={policy.name} capacity={capacity} "
          f"({args.capacity_frac:.0%} of {n_layers * cfg.moe.num_experts})")
    print(f"generated {len(out)} tokens in {dt:.1f}s")
    print(f"cache hit rate: {s.hit_rate:.3f} ({s.hits} hits / {s.misses} "
          f"misses), fetched {s.fetch_bytes / 2**20:.1f} MiB, "
          f"simulated stall {s.sim_stall_s * 1e3:.1f} ms total")


if __name__ == "__main__":
    main()

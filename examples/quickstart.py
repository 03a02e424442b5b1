"""Quickstart: the whole MoE-Beyond pipeline in ~2 minutes on CPU.

1. train a tiny DeepSeek-V2-Lite-family MoE backbone on a topic corpus
2. collect batch-1 expert-activation traces (the paper's dataset schema)
3. train the learned expert-activation predictor (paper §3.2)
4. replay held-out traces through the cache simulator and compare policies

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.configs.base import PredictorConfig
from repro.core.policies import (MoEBeyondPolicy, MoEInfinityPolicy,
                                 NoPrefetchPolicy, OraclePolicy, RandomPolicy)
from repro.core.predictor_train import train_predictor
from repro.core.simulator import SimConfig, simulate
from repro.core.tracing import collect_traces, moe_layer_ids
from repro.data import lm_batches, make_topic_corpus, sample_prompts
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.training.optimizer import make_adamw

enable_compile_cache()      # this script's body is its main
t0 = time.time()

# 1. backbone -------------------------------------------------------------
cfg = get_reduced("deepseek-v2-lite")
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
corpus = make_topic_corpus(cfg.vocab_size, n_topics=4, seed=0)
opt_init, opt_update = make_adamw(lr=3e-3, clip=1.0)
opt_state = opt_init(params)


@jax.jit
def train_step(params, opt_state, tokens):
    def lf(p):
        return model.loss_fn(p, {"tokens": tokens})
    (loss, _), grads = jax.value_and_grad(lf, has_aux=True)(params)
    params, opt_state, _ = opt_update(grads, opt_state, params)
    return params, opt_state, loss


for i, tokens in enumerate(lm_batches(corpus, 16, 64, 80, seed=1)):
    params, opt_state, loss = train_step(params, opt_state,
                                         jnp.asarray(tokens[:, :64]))
print(f"[1] backbone trained: loss {float(loss):.3f} "
      f"({time.time() - t0:.0f}s)")

# 2. traces ---------------------------------------------------------------
prompts = sample_prompts(corpus, 14, 16, seed=2)
traces = collect_traces(model, params, prompts, max_new=48, cache_len=72)
train_tr, test_tr = traces[:10], traces[10:]
n_moe = len(moe_layer_ids(cfg))
print(f"[2] {len(traces)} traces collected, schema (T, L_moe={n_moe}, "
      f"k={cfg.moe.top_k}) ({time.time() - t0:.0f}s)")

# 3. predictor ------------------------------------------------------------
pcfg = PredictorConfig(token_emb_dim=cfg.d_model, num_model_layers=n_moe,
                       num_experts=cfg.moe.num_experts, layer_emb_dim=16,
                       d_model=64, num_layers=2, num_heads=4, d_ff=128,
                       max_seq=72, top_k=cfg.moe.top_k)
pp, hist = train_predictor(train_tr, test_tr, pcfg, epochs=6, batch_size=4,
                           base_lr=5e-3, patience=6)
print(f"[3] predictor: val acc {hist.val_acc[-1]:.3f}, "
      f"F1 {hist.val_f1[-1]:.3f} ({time.time() - t0:.0f}s)")

# 4. simulator ------------------------------------------------------------
sim = SimConfig(num_layers=n_moe, num_experts=cfg.moe.num_experts,
                capacity_fraction=0.2, warm_tokens=6)
print(f"[4] cache simulator @ {sim.capacity_fraction:.0%} expert capacity:")
for policy in [NoPrefetchPolicy(), RandomPolicy(cfg.moe.num_experts, 6),
               MoEInfinityPolicy(train_tr, n_moe, cfg.moe.num_experts, 6),
               MoEBeyondPolicy(pp, pcfg), OraclePolicy()]:
    r = simulate(test_tr, policy, sim)
    print(f"    {r.policy:16s} cache-hit {r.cache_hit_rate:.3f}  "
          f"pred-hit {r.prediction_hit_rate:.3f}  "
          f"stall {r.est_stall_s_per_token * 1e3:.2f} ms/token")
print(f"done in {time.time() - t0:.0f}s")

"""End-to-end driver: train a ~100M-param MoE LM for a few hundred steps.

The ~100M config is the DeepSeek-V2-Lite family scaled to this container
(d_model 256, 8 layers, 16 experts); pass --steps/--batch to scale.

Run:  PYTHONPATH=src python examples/train_backbone.py --steps 200
"""
import argparse

from repro.configs import get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import train


def hundred_m_config():
    cfg = get_reduced("deepseek-v2-lite")
    from repro.configs.base import MLAConfig, MoEConfig
    return cfg.replace(
        num_layers=8, d_model=256, num_heads=8, num_kv_heads=8, head_dim=32,
        vocab_size=8192, d_ff=512,
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=64, rope_head_dim=16,
                      nope_head_dim=32, v_head_dim=32),
        moe=MoEConfig(num_experts=16, top_k=2, num_shared=1,
                      d_ff_expert=512, first_dense_layers=1,
                      d_ff_dense=1024),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--save", default="artifacts/backbone_100m.npz")
    args = ap.parse_args()
    enable_compile_cache()

    import jax

    from repro.models import build_model
    cfg = hundred_m_config()
    model = build_model(cfg)
    n = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    print(f"training {n / 1e6:.0f}M-param MoE "
          f"({cfg.num_layers}L x {cfg.moe.num_experts}e top-{cfg.moe.top_k})")

    # reuse the launcher's loop with this custom config via monkey config:
    import repro.launch.train as LT

    def patched_get_reduced(arch):
        return cfg
    LT.get_reduced = patched_get_reduced
    LT.train("deepseek-v2-lite", reduced=True, steps=args.steps,
             batch_size=args.batch, seq_len=args.seq, lr=3e-3,
             save=args.save)


if __name__ == "__main__":
    main()

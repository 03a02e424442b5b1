"""Train the MoE-Beyond predictor on saved traces with the paper's protocol
(AdamW b2=.98, layerwise LRs, clip 1.0, batch 4, early stopping).

Run:  PYTHONPATH=src python examples/train_predictor.py \
          --traces artifacts/my_traces.npz
"""
import argparse

from repro.configs import get_reduced
from repro.configs.base import PredictorConfig
from repro.core.predictor_train import train_predictor
from repro.core.tracing import load_traces, moe_layer_ids
from repro.launch.compile_cache import enable_compile_cache
from repro.training import checkpoint as ckpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--traces", default="artifacts/my_traces.npz")
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--out", default="artifacts/my_predictor.npz")
    args = ap.parse_args()
    enable_compile_cache()

    traces = load_traces(args.traces)
    n_val = max(1, len(traces) // 5)
    train_tr, val_tr = traces[:-n_val], traces[-n_val:]
    cfg = get_reduced("deepseek-v2-lite")
    n_moe = len(moe_layer_ids(cfg))
    pcfg = PredictorConfig(
        token_emb_dim=traces[0].embeddings.shape[1],
        num_model_layers=traces[0].experts.shape[1],
        num_experts=cfg.moe.num_experts, layer_emb_dim=32, d_model=96,
        num_layers=4, num_heads=8, d_ff=192, max_seq=96,
        top_k=cfg.moe.top_k)
    params, hist = train_predictor(train_tr, val_tr, pcfg,
                                   epochs=args.epochs,
                                   batch_size=args.batch, base_lr=args.lr)
    ckpt.save(args.out, params)
    print(f"best val: loss {min(hist.val_loss):.4f}, "
          f"acc {max(hist.val_acc):.4f}, F1 {max(hist.val_f1):.4f}")
    print(f"saved predictor to {args.out}")


if __name__ == "__main__":
    main()

"""Trace collection (paper Contribution 2): run batch-1 decoding over many
prompts and persist the (token, layer, expert-ids, embedding) trace dataset.

Run:  PYTHONPATH=src python examples/collect_traces.py --n 24 \
          --out artifacts/my_traces.npz
"""
import argparse

from repro.core.tracing import collect_traces, save_traces
from repro.data import make_topic_corpus, sample_prompts
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import train
from repro.configs import get_reduced
from repro.models import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=56)
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--out", default="artifacts/my_traces.npz")
    args = ap.parse_args()
    enable_compile_cache()

    params, _ = train("deepseek-v2-lite", reduced=True,
                      steps=args.train_steps, batch_size=16, seq_len=64)
    cfg = get_reduced("deepseek-v2-lite")
    model = build_model(cfg)
    corpus = make_topic_corpus(cfg.vocab_size, n_topics=8, seed=0)
    prompts = sample_prompts(corpus, args.n, args.prompt_len, seed=42)
    traces = collect_traces(model, params, prompts, max_new=args.max_new,
                            cache_len=args.prompt_len + args.max_new)
    save_traces(args.out, traces)
    total = sum(t.num_tokens * t.experts.shape[1] * t.experts.shape[2]
                for t in traces)
    print(f"saved {len(traces)} traces ({total} activation records) "
          f"to {args.out}")


if __name__ == "__main__":
    main()

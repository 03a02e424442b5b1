"""Whole step: the model FLOPs of every token the traced calls processed
(prompt tokens in prefill chunks, one token per decode lane), over the
traced window times the chip's peak bf16 FLOP/s.

Per token at position p (context S = p + 1), per layer: the MLA
projections in their materialised form (query, with its low-rank pair
where the model has one; latent down-projection; key and value
up-projections; output), attention over S positions, and the FFN (dense
layers) or the router, top-k routed and shared experts (MoE layers); plus
the unembedding. Multiply-adds count two.
"""


def token_flops(conf, s):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    qr, r = conf["q_lora_rank"] or 0, conf["kv_lora_rank"]
    n, rr, v = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                conf["v_head_dim"])
    qk = n + rr
    attn = (2 * (d * qr + qr * h * qk) if qr else 2 * d * h * qk)
    attn += 2 * d * (r + rr) + 2 * r * h * (n + v) + 2 * h * v * d
    attn += 2 * h * s * qk + 2 * h * s * v
    dense = 6 * d * conf["intermediate_size"]
    f, e = conf["moe_intermediate_size"], conf["n_routed_experts"]
    moe = 2 * d * e + 6 * d * f * (conf["num_experts_per_tok"]
                                   + conf["n_shared_experts"])
    n_dense = conf["first_k_dense_replace"]
    n_moe = conf["num_hidden_layers"] - n_dense
    return (conf["num_hidden_layers"] * attn + n_dense * dense + n_moe * moe
            + 2 * d * conf["vocab_size"])


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    flops = sum(token_flops(ctx.conf, p + 1)
                for c in ctx.traced_calls for p in c.positions)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peaks["bf16_flops_per_s"])

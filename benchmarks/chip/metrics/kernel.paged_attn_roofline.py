"""Kernels: the paged MLA attention kernel's share of its roofline, over
the traced part of the window.

The program decodes MLA in its absorbed form: each lane's query (heads x
(kv_lora_rank + rope dims)) attends over the latent pages of its request.
Work per lane at position p (context S = p + 1), for every lane of every
decode step and every token of every prefill chunk, per MLA layer:

* operations: 2 * H * S * (r + rr) for the scores and 2 * H * S * r for
  the weighted sum of latents (r = kv_lora_rank, rr = rope dims);
* bytes: the S latent rows read once, (r + rr) bfloat16 values each, plus
  the lane's query and output.

Time: the device time of ops named by ``OP`` inside the programs in
``MODULES`` (the Pallas kernel's launches in the decode and prefill
programs). Least time over device time, as for every roofline share.
"""
MODULES = ("paged_attn_step", "paged_prefill_step")
OP = r"paged_flash_decode|_kernel_shared"
BYTES = 2  # bfloat16


def work(conf, calls):
    h, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    rr = conf["qk_rope_head_dim"]
    layers = conf["num_hidden_layers"]
    flops = nbytes = 0
    for c in calls:
        for p in c.positions:
            s = p + 1
            flops += layers * (2 * h * s * (r + rr) + 2 * h * s * r)
            nbytes += layers * BYTES * (s * (r + rr) + h * (r + rr) + h * r)
    return flops, nbytes


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.op_s(OP, MODULES)
    flops, nbytes = work(ctx.conf, ctx.traced_calls)
    if t <= 0 or flops <= 0:
        return None
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t

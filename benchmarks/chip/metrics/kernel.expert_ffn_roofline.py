"""Kernels: the expert FFN's share of its roofline, over the traced part of
the window.

Work, from the configuration's shapes and the routing the run observed,
per MoE layer of every decode step and prefill chunk (padding lanes are
not counted):

* operations: 2 * 3 * d * f per (token, routed expert) pair, and
  2 * 3 * d * (shared * f) per token for the shared experts;
* bytes: each distinct routed expert's three bfloat16 matrices
  (3 * d * f * 2), the shared experts' (3 * d * shared * f * 2), and the
  token activations read and written (3 * tokens * d * 2).

Time: the device time of the programs in ``MODULES``, which hold the
expert FFN (the program's slot-buffer gather and its FFN, plus the shared
experts). The least time is the larger of operations over peak FLOP/s and
bytes over peak bandwidth; the share is least time over device time.
"""
MODULES = ("expert_from_slots",)
BYTES = 2  # bfloat16


def work(conf, calls):
    d, f = conf["hidden_size"], conf["moe_intermediate_size"]
    shared = conf["n_shared_experts"]
    flops = nbytes = 0
    for c in calls:
        tokens = len(c.rids) if c.kind == "decode" else len(c.positions)
        for pairs, distinct in c.routes:
            flops += 6 * d * f * (pairs + tokens * shared)
            nbytes += BYTES * (3 * d * f * (distinct + shared)
                               + 3 * tokens * d)
    return flops, nbytes


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.module_s(MODULES)
    flops, nbytes = work(ctx.conf, ctx.traced_calls)
    if t <= 0 or flops <= 0:
        return None
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t

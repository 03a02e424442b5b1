"""Tests of the chip benchmark, run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="chip-tests-jax-cache-"))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import spec  # noqa: E402


def tiny_cell(q_lora=None, dtype="bfloat16", tier0_share=0.5):
    """The Lite offload cell at widths a CPU test can hold: every path of
    the full cell, with chat traffic shortened to match."""
    cell = spec.cell("dsv2lite-chat-offload")
    conf = dict(cell["config"])
    conf.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                q_lora_rank=q_lora, kv_lora_rank=32, qk_rope_head_dim=16,
                qk_nope_head_dim=32, v_head_dim=32, intermediate_size=128,
                moe_intermediate_size=32, n_routed_experts=8,
                n_shared_experts=1, num_experts_per_tok=2,
                num_hidden_layers=3, vocab_size=512)
    conf["assumed"] = dict(conf["assumed"], dtype=dtype,
                           tier0_share=tier0_share)
    mix = dict(cell["mix"])
    mix["prompt_tokens"] = dict(mix["prompt_tokens"], mean=24, min=8,
                                max=64)
    mix["output_tokens"] = dict(mix["output_tokens"], mean=8, min=4,
                                max=16)
    mix["openers"] = [[34, 4], [51, 4], [68, 4], [97, 4]]
    return dict(cell, config=conf, mix=mix)

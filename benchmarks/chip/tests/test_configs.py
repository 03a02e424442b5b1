"""Each configuration file holds its source's catalog entry: every key and
nested group as published, the run's changes listed under ``reduced``, and
the program built from it at the published widths."""
import glob
import os

import pytest

from benchmarks.chip import spec

CONFIGS = sorted(glob.glob(str(spec.HERE / "configs" / "*.json")))
WIDTHS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
          "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
          "qk_nope_head_dim", "v_head_dim", "moe_intermediate_size",
          "intermediate_size", "n_routed_experts", "num_experts_per_tok",
          "n_shared_experts", "vocab_size")
EXTRA = {"name", "source", "why", "deployment", "reduced", "assumed",
         "config"}


def _nulls(obj, path=()):
    if obj is None:
        yield path
    elif isinstance(obj, dict):
        for k, v in obj.items():
            yield from _nulls(v, path + (k,))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _nulls(v, path + (i,))


@pytest.fixture(params=[os.path.basename(p)[:-5] for p in CONFIGS])
def conf(request):
    return spec.config_file(request.param)


def test_every_source_key_is_held_and_only_reduced_keys_change(conf):
    src = conf["config"]
    assert set(conf) - EXTRA == set(src)
    changed = {k for k in src if conf[k] != src[k]}
    assert changed <= set(conf["reduced"])
    for k, v in src.items():
        if isinstance(v, dict):          # nested groups are copied whole
            assert conf[k] == v, k


def test_reduced_lists_every_run_departure(conf):
    from benchmarks.chip import serve
    src = conf["config"]
    changed = {k for k in src if conf[k] != src[k]}
    assert set(conf["reduced"]) == changed | set(serve.departures(src))
    for k, entry in conf["reduced"].items():
        assert entry["why"] and "run" in entry and "source" in entry, k


def test_widths_are_the_sources(conf):
    from benchmarks.chip import serve
    src = conf["config"]
    for k in WIDTHS:
        assert conf[k] == src[k], k
    cfg = serve.model_config(conf)
    got = {"hidden_size": cfg.d_model,
           "num_attention_heads": cfg.num_heads,
           "num_key_value_heads": cfg.num_kv_heads,
           "kv_lora_rank": cfg.mla.kv_lora_rank,
           "q_lora_rank": cfg.mla.q_lora_rank or None,
           "qk_rope_head_dim": cfg.mla.rope_head_dim,
           "qk_nope_head_dim": cfg.mla.nope_head_dim,
           "v_head_dim": cfg.mla.v_head_dim,
           "moe_intermediate_size": cfg.moe.d_ff_expert,
           "intermediate_size": cfg.moe.d_ff_dense,
           "n_routed_experts": cfg.moe.num_experts,
           "num_experts_per_tok": cfg.moe.top_k,
           "n_shared_experts": cfg.moe.num_shared,
           "vocab_size": cfg.vocab_size}
    assert got == {k: src[k] for k in WIDTHS}
    assert cfg.num_layers == conf["num_hidden_layers"]
    assert cfg.moe.first_dense_layers == src["first_k_dense_replace"]


def test_widths_match_the_repositorys_model_files(conf):
    from benchmarks.chip import serve
    from repro.configs import deepseek_v2_236b, deepseek_v2_lite
    preset = {"https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/"
              "main/config.json": deepseek_v2_lite.CONFIG,
              "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/"
              "config.json": deepseek_v2_236b.CONFIG}[conf["source"]]
    cfg = serve.model_config(conf)
    assert cfg.mla == preset.mla
    assert (cfg.d_model, cfg.num_heads, cfg.vocab_size) == (
        preset.d_model, preset.num_heads, preset.vocab_size)
    for k in ("num_experts", "top_k", "num_shared", "d_ff_expert",
              "first_dense_layers", "d_ff_dense"):
        assert getattr(cfg.moe, k) == getattr(preset.moe, k), k


def test_no_null_the_source_does_not_hold(conf):
    src_nulls = {p for p in _nulls(conf["config"])}
    for path in _nulls(conf):
        inner = path[1:] if path[0] == "config" else path
        assert inner in src_nulls, path


def test_benchmark_lists_the_files_changes_and_no_width():
    bench = spec.benchmark()
    for c in bench["configs"]:
        conf = spec.config_file(c["name"])
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert c["source"] == conf["source"]
        src = conf["config"]
        changed = {k for k in src if conf[k] != src[k]}
        assert changed <= set(c["reduced"]) <= set(conf["reduced"])
        for k in c["reduced"]:
            assert k not in WIDTHS and not k.endswith(("_dim", "_rank"))


def test_a_departure_not_listed_is_refused():
    from benchmarks.chip import serve
    conf = spec.config_file("dsv2-lite-d5-offload")
    conf["reduced"] = {k: v for k, v in conf["reduced"].items()
                       if k != "norm_topk_prob"}
    with pytest.raises(ValueError, match="norm_topk_prob"):
        serve.model_config(conf)

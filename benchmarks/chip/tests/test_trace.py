"""The reduction from a trace to metrics, on a small synthetic trace, and
the roofline counts against hand-computed numbers at DeepSeek-V2-Lite's
widths."""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmarks.chip import spec
from benchmarks.chip.serve import Call
from benchmarks.chip.tests.conftest import ROOT
from benchmarks.chip.trace import Trace, union

MS = 1e6  # nanoseconds


def synthetic():
    """10 ms window; ops busy 0-2, 1-3 (overlap), 5-6 ms; the kernel op
    sits inside the paged program, another op of the same name outside."""
    ops = {0: [("fusion.1", 0 * MS, 2 * MS), ("fusion.2", 1 * MS, 3 * MS),
               ("paged_flash_decode_pallas", 5 * MS, 5.5 * MS),
               ("paged_flash_decode_pallas", 5.5 * MS, 6 * MS),
               ("fusion.3", 12 * MS, 13 * MS)]}
    modules = {0: [("jit_expert_from_slots(17)", 0 * MS, 3 * MS),
                   ("jit_paged_attn_step(4)", 5 * MS, 5.5 * MS)]}
    host = [("bench.traced", 0 * MS, 10 * MS),
            ("bench.decode_step", 0 * MS, 4.5 * MS),
            ("bench.prefill_chunk", 6.5 * MS, 10 * MS)]
    return Trace(ops, modules, host)


def test_busy_is_the_union_of_op_intervals_in_the_window():
    t = synthetic()
    assert t.window_s == pytest.approx(0.010)
    assert t.busy(0) == [(0.0, 3 * MS), (5 * MS, 6 * MS)]
    assert t.busy_s() == pytest.approx(0.004)
    idle = spec.reader("device.idle_share").read(SimpleNamespace(trace=t))
    assert idle == pytest.approx(60.0)
    assert union([(5, 9), (1, 2), (2, 4)], 0, 8) == [(1, 4), (5, 8)]


def test_kernel_time_sums_named_ops_inside_named_programs():
    t = synthetic()
    assert t.op_s("paged_flash_decode", ("paged_attn_step",)) == \
        pytest.approx(0.0005)
    assert t.module_s(("expert_from_slots",)) == pytest.approx(0.003)
    assert t.top_modules()[0] == ["expert_from_slots", pytest.approx(0.003)]


def test_idle_time_is_summed_by_the_host_span_around_each_gap():
    t = synthetic()
    t.host.append(("DevicePut", 3.2 * MS, 3.9 * MS))
    gaps = t.idle_gaps()
    # idle 3-5 ms (middle 4 ms: inside the decode step, no inner span)
    # and 6-10 ms (middle 8 ms: inside the prefill chunk)
    assert gaps == [["bench.prefill_chunk", pytest.approx(0.004)],
                    ["bench.decode_step", pytest.approx(0.002)]]
    assert t.labels([3.5 * MS, 4.0 * MS, 4.8 * MS]) == [
        "DevicePut", "bench.decode_step", "bench.between_calls"]


def test_a_trace_needs_exactly_one_window():
    with pytest.raises(ValueError):
        Trace({0: []}, {}, [])


LITE = spec.config_file("dsv2-lite-d5-offload")
V5E = spec.peaks("TPU v5 lite")


def test_expert_ffn_work_by_hand_at_lite_widths():
    mod = spec.reader("kernel.expert_ffn_roofline")
    # one decode step of 4 lanes: per MoE layer 24 token-expert pairs over
    # 20 distinct experts; d = 2048, f = 1408, 2 shared experts
    call = Call("decode", 0, 1, [0, 1, 2, 3], [10, 11, 12, 13],
                [(24, 20)] * 4, traced=True)
    flops, nbytes = mod.work(LITE, [call])
    per_layer_flops = 2 * 3 * 2048 * 1408 * (24 + 4 * 2)
    per_layer_bytes = 2 * (3 * 2048 * 1408 * (20 + 2) + 3 * 4 * 2048)
    assert flops == 4 * per_layer_flops == 4 * 553_648_128
    assert nbytes == 4 * per_layer_bytes == 4 * 380_682_240
    trace = SimpleNamespace(module_s=lambda names: 0.01)
    share = mod.read(SimpleNamespace(trace=trace, conf=LITE, peaks=V5E,
                                     traced_calls=[call]))
    # memory-bound: 4 * 380.7 MB at 819 GB/s is 1.859 ms of 10 ms
    assert share == pytest.approx(100 * 4 * per_layer_bytes / 819e9 / 0.01)


def test_paged_attention_work_by_hand_at_lite_widths():
    mod = spec.reader("kernel.paged_attn_roofline")
    call = Call("decode", 0, 1, [0], [99], [], traced=True)
    flops, nbytes = mod.work(LITE, [call])
    # 5 MLA layers, 16 heads, context 100, latent 512 + rope 64
    assert flops == 5 * (2 * 16 * 100 * 576 + 2 * 16 * 100 * 512)
    assert nbytes == 5 * 2 * (100 * 576 + 16 * 576 + 16 * 512)


def test_model_flops_by_hand_at_lite_widths():
    mod = spec.reader("model.mfu")
    d, h, v = 2048, 16, 102400
    attn = (2 * d * h * 192 + 2 * d * 576 + 2 * 512 * h * 256
            + 2 * h * 128 * d + 2 * h * 1 * 192 + 2 * h * 1 * 128)
    dense = 6 * d * 10944
    moe = 2 * d * 64 + 6 * d * 1408 * (6 + 2)
    assert mod.token_flops(LITE, 1) == 5 * attn + dense + 4 * moe + 2 * d * v


def test_shares_read_nothing_without_a_trace_or_work():
    ctx = SimpleNamespace(trace=None)
    for name in ("kernel.expert_ffn_roofline", "kernel.paged_attn_roofline",
                 "model.mfu", "device.idle_share"):
        assert spec.reader(name).read(ctx) is None
    ctx = SimpleNamespace(trace=SimpleNamespace(module_s=lambda n: 0.0),
                          conf=LITE, peaks=V5E, traced_calls=[])
    assert spec.reader("kernel.expert_ffn_roofline").read(ctx) is None


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="not in peaks.json"):
        spec.peaks("TPU v9 imaginary")


def test_a_cpu_run_exits_nonzero_and_names_the_missing_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "dsv2lite-chat-offload", "--seed", "2147483700", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""

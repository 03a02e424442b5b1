"""How ``correct`` is decided, at a size a CPU test run can hold.

The reference agrees with the program's own forward pass, a sound run
comes out correct, the int8 and fp8 controls, judged by the same code,
come out not correct, and a run whose
timed path is broken underneath comes out not correct for each fault a
serving cell can have: a decode step that returns its state (the KV cache)
unchanged, half of the batch's lanes left without their experts, and a
token altered where it is produced."""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import bench, reference, serve
from benchmarks.chip.tests.conftest import tiny_cell

SEED = 2**31 + 77


def run(cell, **kw):
    return bench.measure(cell, SEED, 3.0, False, time.perf_counter(),
                         log=lambda s: None, **kw)


def test_reference_matches_the_programs_forward_pass():
    from repro.models import transformer as T
    for q_lora in (None, 32):
        conf = tiny_cell(q_lora=q_lora, dtype="float32")["config"]
        cfg = serve.model_config(conf)
        # capacity for every token, so the model's dispatch drops none
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.num_experts / cfg.moe.top_k))
        params = T.lm_init(reference.weight_key(SEED), cfg)
        toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
        want, *_ = T.lm_apply(params, cfg, jnp.asarray(toks[None]),
                              mode="full")
        want = np.asarray(want[0])
        served = np.argmax(want, -1)
        seq = reference.Sequence_(toks.tolist(), served[9:].tolist(), 9,
                                  want[9:])
        got = reference.compare(conf, SEED, [seq])
        np.testing.assert_allclose(got["program"]["gap"][0], 0.0, atol=1e-4)
        rel = np.sqrt(got["program"]["d2"][0].sum()
                      / got["program"]["r2"][0].sum())
        assert rel < 1e-4
        assert got["ref_argmax_match"][0].all()


def test_a_sound_run_is_correct_and_each_control_is_not():
    # hidden width 256: int8 scales a row by its largest entry, and over 64
    # entries that rounds finer than over the served model's 2048
    cell = tiny_cell(dtype="float32")
    cell["config"].update(hidden_size=256, intermediate_size=512,
                          moe_intermediate_size=128)
    out = run(cell, controls=reference.CONTROLS)
    assert out["correct"], out["checks"]
    assert out["served_tokens_compared"] > 20
    assert out["checks"]["logits_rel_l2"]["value"] < 1e-4
    assert out["checks"]["median_rel_l2"]["value"] < 1e-4
    assert list(out)[-1] == "checks"
    for mode in reference.CONTROLS:
        ctl = out["controls"][mode]
        assert not ctl["correct"], (mode, ctl["checks"])
        assert ctl["compared"] == out["served_tokens_compared"]


def test_the_bfloat16_program_at_small_size_passes_its_own_check():
    out = run(tiny_cell())
    assert out["failed"] == 0
    assert out["requests_compared"] >= 2


@pytest.fixture
def broken(monkeypatch):
    def apply(fault):
        from repro.serving import engine as E
        from repro.serving import scheduler as S
        if fault == "token":
            def altered(logits, temperature, rng):
                return (int(np.argmax(logits)) + 1) % logits.shape[-1]
            monkeypatch.setattr(S, "sample_token", altered)
        elif fault == "state":
            step = E.DecodeCore.step

            def unchanged(self, caches, *a, **kw):
                logits, _, experts = step(self, list(caches), *a, **kw)
                return logits, caches, experts
            monkeypatch.setattr(E.DecodeCore, "step", unchanged)
        elif fault == "half":
            build = E.DecodeCore._build_fns

            def halved(self):
                build(self)
                full = self._expert

                def expert(x_norm, weights, slot_idx, *rest):
                    out = full(x_norm, weights, slot_idx, *rest)
                    x = rest[-1]
                    keep = jnp.arange(x.shape[0]) < (x.shape[0] + 1) // 2
                    return jnp.where(keep[:, None, None], out, x)
                self._expert = expert
            monkeypatch.setattr(E.DecodeCore, "_build_fns", halved)
    return apply


@pytest.mark.parametrize("fault", ["state", "half", "token"])
def test_a_broken_timed_path_is_not_correct(broken, fault):
    broken(fault)
    out = run(tiny_cell(dtype="float32"))
    assert not out["correct"], out["checks"]


def test_the_sample_is_finished_mix_requests_with_the_longest():
    from types import SimpleNamespace as R
    reqs = {0: R(index=0, t_done=1.0, served=[0] * 99),    # an opener
            1: R(index=1, t_done=5.0, served=[0] * 30),
            2: R(index=1, t_done=None, served=[0] * 80),   # in flight
            3: R(index=2, t_done=9.0, served=[0] * 60),
            4: R(index=1, t_done=12.0, served=[0] * 90),   # after the close
            5: R(index=2, t_done=None, served=[])}         # not yet served
    got = bench._sample(reqs, 10.0, SEED)
    assert got[:2] == [3, 1] and sorted(got[2:]) == [2, 4]
    for n in range(1, 20):
        reqs[10 + n] = R(index=3, t_done=2.0, served=[0] * n)
    got = bench._sample(reqs, 10.0, SEED)
    assert got[0] == 3 and len(got) == reference.BATCH
    assert not {0, 2, 4, 5} & set(got)

"""The system under test, built from a configuration file and driven by a
mix's closed-loop clients through its normal serving entry,
``BatchedOffloadEngine`` (scheduler, chunked prefill, paged MLA pool and
kernel, the MoE-Beyond predictor, host expert store and tier-0 slot
buffer, expert FFN, unembedding and greedy sampling).

Its ``Driver`` stands in for ``DecodeCore.step`` and ``prefill_chunk``
only to watch them: it times each call (each returns host logits, so the
device has finished), keeps what each served with the logits it was
served from, submits a client's next request the moment its last token
is out, and opens and closes the measured window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Dict, List, Optional, Set

import jax
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, PredictorConfig
from repro.core.policies import OnlineMoEBeyondPolicy
from repro.core.predictor import predictor_init
from repro.models import build_model
from repro.serving.config import ServeConfig
from repro.serving.engine import init_offload_params
from repro.serving.scheduler import BatchedOffloadEngine

from benchmarks.chip import traffic
from benchmarks.chip.reference import weight_key

# What the program does where a DeepSeek-V2 configuration could say
# otherwise. A file whose value differs from these is refused unless its
# ``reduced`` names the key: the run then departs from the source there.
RUN_VALUES = {
    "rope_scaling": None,            # plain RoPE with rope_theta alone
    "norm_topk_prob": True,          # top-k weights always renormalised
    "topk_method": "greedy",         # plain top-k over all experts
    "n_group": 1,
    "topk_group": 1,
    "routed_scaling_factor": 1,
    "scoring_func": "softmax",
    "hidden_act": "silu",
    "attention_bias": False,
    "moe_layer_freq": 1,
    "tie_word_embeddings": False,
}


def departures(conf: dict) -> Dict[str, object]:
    """Source keys whose value the program does not honour -> what the
    run does instead."""
    return {k: v for k, v in RUN_VALUES.items() if conf.get(k, v) != v}


def model_config(conf: dict) -> ModelConfig:
    """The program's ``ModelConfig`` for a configuration file, every width
    as the file gives it."""
    unlisted = set(departures(conf)) - set(conf.get("reduced", {}))
    if unlisted:
        raise ValueError(f"{conf['name']}: the program departs from "
                         f"{sorted(unlisted)}, which 'reduced' does not list")
    return ModelConfig(
        name=conf["name"], arch_type="moe",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["v_head_dim"], d_ff=conf["moe_intermediate_size"],
        vocab_size=conf["vocab_size"], block_pattern=("mla",),
        mla=MLAConfig(q_lora_rank=conf["q_lora_rank"] or 0,
                      kv_lora_rank=conf["kv_lora_rank"],
                      rope_head_dim=conf["qk_rope_head_dim"],
                      nope_head_dim=conf["qk_nope_head_dim"],
                      v_head_dim=conf["v_head_dim"]),
        moe=MoEConfig(num_experts=conf["n_routed_experts"],
                      top_k=conf["num_experts_per_tok"],
                      num_shared=conf["n_shared_experts"],
                      d_ff_expert=conf["moe_intermediate_size"],
                      first_dense_layers=conf["first_k_dense_replace"],
                      d_ff_dense=conf["intermediate_size"]),
        rope_theta=float(conf["rope_theta"]), norm_eps=conf["rms_norm_eps"],
        dtype=conf["assumed"]["dtype"], source=conf["source"])


def tier0_slots(cfg: ModelConfig, assumed: dict) -> int:
    n_moe = cfg.num_layers - cfg.moe.first_dense_layers
    return max(assumed["max_batch"] * cfg.moe.top_k,
               round(assumed["tier0_share"] * n_moe * cfg.moe.num_experts))


def build(conf: dict, seed: int, telemetry=None, phases=None):
    """(engine, ModelConfig, PredictorConfig, predictor params): weights
    drawn on the device from the seed by the program's own init, routed
    experts left in host memory, the predictor's weights from the seed.
    ``phases`` (a dict) gets the seconds of the weights and the engine."""
    phases = {} if phases is None else phases
    cfg = model_config(conf)
    assumed = conf["assumed"]
    t = time.perf_counter()
    params = init_offload_params(cfg, weight_key(seed))
    jax.block_until_ready(params)
    phases["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    pcfg = PredictorConfig().replace(
        token_emb_dim=cfg.d_model, num_experts=cfg.moe.num_experts,
        num_model_layers=cfg.num_layers - cfg.moe.first_dense_layers)
    predictor = predictor_init(jax.random.fold_in(weight_key(seed), 1), pcfg)
    engine = BatchedOffloadEngine(
        build_model(cfg), params,
        lambda: OnlineMoEBeyondPolicy(predictor, pcfg),
        tier0_slots(cfg, assumed),
        serve=ServeConfig(max_batch=assumed["max_batch"],
                          telemetry=telemetry))
    del params
    gc.collect()
    phases["engine"] = time.perf_counter() - t
    return engine, cfg, pcfg, predictor


def warm_predictor(predictor, pcfg: PredictorConfig, max_batch: int) -> None:
    """Run the predictor's batched forward at every (rows, length) bucket
    serving can ask for, so none compiles inside the window."""
    pols = [OnlineMoEBeyondPolicy(predictor, pcfg) for _ in range(max_batch)]
    emb = np.zeros(pcfg.token_emb_dim, np.float32)
    seen, length = 0, 1
    while length <= pcfg.max_seq:
        for t in range(seen, length):
            for p in pols:
                p.observe(t, 0, (), emb)
        seen = length
        for rows in range(1, max_batch + 1):
            OnlineMoEBeyondPolicy.predict_many_layers(pols[:rows], [0])
        length *= 2


class WindowClosed(Exception):
    """Raised out of the engine's loop when the measured window ends."""


@dataclasses.dataclass
class Request:
    client: int
    index: int
    prompt: List[int]
    n_out: int                       # tokens the request serves
    t_submit: float
    served: List[tuple] = dataclasses.field(default_factory=list)
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    fed: Dict[int, int] = dataclasses.field(default_factory=dict)
    finite: bool = True
    t_done: Optional[float] = None   # when its last token was served


@dataclasses.dataclass
class Call:
    kind: str                        # "decode" | "prefill"
    t0: float
    t1: float
    rids: List[int]
    positions: List[int]             # every position the call processed
    routes: List[tuple]              # per MoE layer: (token-expert pairs,
    #                                  distinct experts)
    traced: bool = False


def _routes(per_layer_sets) -> List[tuple]:
    out = []
    for sets in per_layer_sets:
        pairs = sum(len(s) for s in sets)
        out.append((pairs, len(set().union(*map(set, sets))) if sets else 0))
    return out


class Driver:
    """Closed-loop clients on one engine, and the window over them.

    Each client's first request (its opener, a warm-up request) is
    submitted at the start, and each client sends its mix requests after
    it; the window opens at the end of the decode step in which the last
    opener serves its last token, and closes at the end of the first call
    that ends ``seconds`` later. The logits of every token served to a mix
    request are kept for the check after the window. ``tracer``
    (optional) is started when the window opens and stopped ``trace_s``
    later."""

    def __init__(self, engine: BatchedOffloadEngine, mix: dict, seed: int,
                 vocab: int, seconds: float, tracer=None,
                 trace_s: float = 0.0, on_open=None):
        self.engine = engine
        self.mix = mix
        self.seed = seed
        self.vocab = vocab
        self.seconds = seconds
        self.tracer = tracer
        self.trace_s = trace_s
        self.on_open = on_open
        self.plans = traffic.client_plans(mix)
        self.requests: Dict[int, Request] = {}
        self.calls: List[Call] = []
        self.openers: Set[int] = set()
        self.waiting: Set[int] = set()     # openers yet to finish
        self.w0: Optional[float] = None
        self.w1: Optional[float] = None
        self.t_trace: Optional[float] = None

    # -- clients ---------------------------------------------------------
    def _submit(self, client: int, index: int, now: float) -> None:
        plan = self.plans[client]
        i = index if index < len(plan) else 1 + (index - 1) % (len(plan) - 1)
        plen, n_out = plan[i]
        prompt = traffic.prompt_tokens(self.seed, client, index, plen,
                                       self.vocab)
        rid = self.engine.submit(prompt, n_out - 1)
        self.requests[rid] = Request(client, index, prompt, n_out, now)

    # -- the watched calls -----------------------------------------------
    def _step(self, caches, *args, **kw):
        t0 = time.perf_counter()
        with self._annotate("bench.decode_step"):
            logits, caches, experts = self._core_step(caches, *args, **kw)
        t1 = time.perf_counter()
        rids, pos, toks = kw["rids"], kw["pos"], kw["tokens"]
        for rid, p, tok, lg in zip(rids, pos, toks, logits):
            r = self.requests[rid]
            plen = len(r.prompt)
            if p >= plen:
                r.fed[p] = int(tok)
            if p >= plen - 1:
                r.served.append((p, int(np.argmax(lg)), t1))
                if rid not in self.openers:
                    r.logits.append(lg)
                r.finite &= bool(np.isfinite(lg).all())
                if p == plen + r.n_out - 2:
                    r.t_done = t1
                    self.waiting.discard(rid)
                    self._submit(r.client, r.index + 1, t1)
        n_moe = len(experts[0]) if experts else 0
        self.calls.append(Call("decode", t0, t1, list(rids), list(pos),
                               _routes([[e[li] for e in experts]
                                        for li in range(n_moe)]),
                               self.t_trace is not None))
        self._tick(t1)
        return logits, caches, experts

    def _chunk(self, caches, table, t0_pos, tokens, policy, rid):
        t0 = time.perf_counter()
        with self._annotate("bench.prefill_chunk"):
            out = self._core_chunk(caches, table, t0_pos, tokens, policy,
                                   rid)
        t1 = time.perf_counter()
        n = len(tokens)
        self.calls.append(Call("prefill", t0, t1, [rid],
                               list(range(t0_pos, t0_pos + n)),
                               _routes(out[2]), self.t_trace is not None))
        self._tick(t1)
        return out

    def _annotate(self, name):
        if self.t_trace is None:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def _tick(self, now: float) -> None:
        if self.w0 is None:
            if not self.waiting:
                self.w0 = now
                if self.on_open is not None:
                    self.on_open()
                if self.tracer is not None:
                    self.tracer.start()
                    self.t_trace = time.perf_counter()
            return
        if self.t_trace is not None and now >= self.t_trace + self.trace_s:
            self.tracer.stop()
            self.t_trace = None
        if now >= self.w0 + self.seconds:
            self.w1 = now
            if self.t_trace is not None:
                self.tracer.stop()
                self.t_trace = None
            raise WindowClosed

    # -- the run ---------------------------------------------------------
    def run(self) -> None:
        core = self.engine.core
        self._core_step, self._core_chunk = core.step, core.prefill_chunk
        core.step, core.prefill_chunk = self._step, self._chunk
        try:
            now = time.perf_counter()
            for c in range(self.mix["clients"]):
                self._submit(c, 0, now)
            self.openers = set(self.requests)
            self.waiting = set(self.openers)
            self.engine.run(traffic.max_tokens(self.mix))
            raise RuntimeError("the engine ran out of work before the "
                               "window closed")
        except WindowClosed:
            pass
        finally:
            core.step, core.prefill_chunk = self._core_step, self._core_chunk


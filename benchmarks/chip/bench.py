"""One run of one cell: set-up, the measured window, the per-layer readers,
and the check of what the window served against the plain reference.

``measure`` returns the result line's object. Its numbers compared for
``correct`` are in ``LIMITS``; how each limit was set is in ``PERF.md``.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Dict, List

import jax
import numpy as np

from benchmarks.chip import reference, serve, spec
from benchmarks.chip.trace import Trace

TRACE_S = 12.0        # seconds of the window a traced run traces
# The numbers compared for ``correct``: the served logits' distance from
# the reference's, over all compared positions, relative to the
# reference's norm; the median over compared positions of that distance
# at each position (a routing flip under rounding moves a few positions
# far, and the median reads past them); the mean over compared tokens of
# how far below the reference's best logit the served token lies (in
# logits); and how many requests broke (exact). Each limit lies between
# the program's highest reading on the chip and the lowest reading of a
# control that reads three times that or more; PERF.md, "How correct is
# decided", has the readings.
LIMITS = {"logits_rel_l2": 0.12, "median_rel_l2": 0.045,
          "mean_logit_gap": 0.035, "failed_requests": 0}


class Compiles:
    """Counts the programs JAX traces and compiles while it listens."""

    def __init__(self):
        self.traced = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.traced += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


class Tracer:
    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chip-trace-")
        self._ann = None

    def start(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()

    def stop(self):
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read(self) -> Trace:
        try:
            return Trace.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


COUNTERS = ("cache.hit", "cache.miss", "predictor.tp", "predictor.fp")


def _snapshot(engine, tel, compiles) -> dict:
    snap = {"fetch_bytes": engine.stats.fetch_bytes,
            "traced": compiles.traced, "compiled": compiles.compiled}
    if tel is not None:
        snap.update({k: tel.total(k) for k in COUNTERS})
    return snap


def _itl_and_tokens(requests, w0, w1):
    gaps, tokens = [], 0
    for r in requests.values():
        times = [t for _, _, t in r.served if w0 < t <= w1]
        tokens += len(times)
        gaps.extend(np.diff(times))
    return gaps, tokens


def _sample(requests, w1, seed) -> List[int]:
    """Mix requests served by the window's close, up to one reference
    batch: those finished by then, the longest first and the others in an
    order drawn from the seed, then those still in flight (each with the
    tokens it had been served), in an order drawn from the seed."""
    served = [rid for rid, r in requests.items() if r.index > 0 and r.served]
    done = [rid for rid in served
            if requests[rid].t_done is not None and requests[rid].t_done <= w1]
    rng = np.random.default_rng([seed, 2])
    out = []
    if done:
        longest = max(done, key=lambda rid: (len(requests[rid].served), -rid))
        out = [longest] + rng.permutation(
            sorted(set(done) - {longest})).tolist()
    out += rng.permutation(sorted(set(served) - set(done))).tolist()
    return out[:reference.BATCH]


def _faults(r) -> List[str]:
    """What is wrong with one request's record, as the window saw it."""
    out = []
    plen = len(r.prompt)
    pos = [p for p, _, _ in r.served]
    if pos != list(range(plen - 1, plen - 1 + len(pos))):
        out.append("served positions not contiguous")
    for p, tok, _ in r.served:
        if p + 1 in r.fed and r.fed[p + 1] != tok:
            out.append(f"position {p + 1} was fed {r.fed[p + 1]}, "
                       f"not the served {tok}")
            break
    if not r.finite:
        out.append("non-finite logits")
    return out


def check(conf: dict, seed: int, requests: dict, w1: float,
          controls=()) -> dict:
    """Compare the served logits and tokens of the sampled requests with
    the plain reference, and each control's with it; the numbers of the
    program (``"program"``) and of each control, by name."""
    rids = _sample(requests, w1, seed)
    seqs = []
    for rid in rids:
        r = requests[rid]
        plen = len(r.prompt)
        served = [tok for _, tok, _ in r.served]
        fed = [r.fed.get(p, -1) for p in range(plen, plen + len(served) - 1)]
        seqs.append(reference.Sequence_(r.prompt + fed, served, plen - 1,
                                        np.stack(r.logits)))
    names = ("program",) + tuple(controls)
    if not seqs:
        return {"rids": [], **{n: numbers([], [], []) for n in names}}
    out = reference.compare(conf, seed, seqs, controls)
    return {"rids": rids, **{n: numbers(**out[n]) for n in names}}


def numbers(gap, d2, r2) -> Dict[str, float]:
    """The compared numbers, and others printed beside them, from per
    position readings (lists of arrays, one per sequence)."""
    if not len(gap):
        return {"logits_rel_l2": float("inf"),
                "median_rel_l2": float("inf"),
                "mean_logit_gap": float("inf"),
                "widest_logit_gap": float("inf"), "off_argmax": 1.0,
                "compared": 0}
    gap, d2, r2 = (np.concatenate(a) for a in (gap, d2, r2))
    return {"logits_rel_l2": float(np.sqrt(d2.sum() / r2.sum())),
            "median_rel_l2": float(np.median(np.sqrt(d2 / r2))),
            "mean_logit_gap": float(gap.mean()),
            "widest_logit_gap": float(gap.max()),
            "off_argmax": float((gap > 0).mean()),
            "compared": int(gap.size)}


def verdict(nums: dict, failed: int):
    """(checks, correct): each compared number beside its limit, and
    whether every one keeps to it, for the program or a control alike."""
    values = dict(nums, failed_requests=failed)
    checks = {k: {"value": values[k], "limit": lim}
              for k, lim in LIMITS.items()}
    return checks, bool(nums["compared"]) and all(
        c["value"] <= c["limit"] for c in checks.values())


def measure(cell: dict, seed: int, seconds: float, trace: bool,
            t_start: float, log=None, controls=()) -> dict:
    """One run; returns the result line's object. ``log`` gets lines for
    standard error. ``controls`` (modes of ``reference.CONTROLS``) also
    reads each control on the same prompts and served tokens, judged by
    the same ``verdict``, under ``controls``."""
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.telemetry import Telemetry

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    conf, mix = cell["config"], cell["mix"]
    dev = jax.devices()[0]
    peaks = spec.peaks(dev.device_kind) if dev.platform == "tpu" else None
    enable_compile_cache()
    compiles = Compiles()
    phases = {"start": time.perf_counter() - t_start}

    tel = Telemetry() if trace else None
    engine, cfg, pcfg, predictor = serve.build(conf, seed, tel, phases)
    t = time.perf_counter()
    serve.warm_predictor(predictor, pcfg, engine.max_batch)
    phases["predictor_warm_up"] = time.perf_counter() - t

    snaps = {}
    tracer = Tracer() if trace else None
    driver = serve.Driver(
        engine, mix, seed, cfg.vocab_size, seconds, tracer, TRACE_S,
        on_open=lambda: snaps.__setitem__(
            "open", _snapshot(engine, tel, compiles)))
    t = time.perf_counter()
    driver.run()
    snaps["close"] = _snapshot(engine, tel, compiles)
    w0, w1 = driver.w0, driver.w1
    phases["openers"] = w0 - t
    phases["window"] = w1 - w0
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    delta = {k: snaps["close"][k] - snaps["open"][k] for k in snaps["close"]}
    spans = {}
    if tel is not None:
        lo, hi = tel.rel(w0), tel.rel(w1)
        for s in tel.spans():
            if s.name in ("decode_step", "prefill_chunk") and lo < s.t1_s <= hi:
                spans.setdefault(s.name, []).append(s.dur_s)
    tr = None
    if tracer is not None:
        t = time.perf_counter()
        tr = tracer.read()
        phases["trace_read"] = time.perf_counter() - t

    requests = driver.requests
    calls = [c for c in driver.calls if w0 < c.t1 <= w1]
    gaps, out_tokens = _itl_and_tokens(requests, w0, w1)
    attempted = [rid for rid, r in requests.items()
                 if r.t_submit <= w1 and not (r.t_done is not None
                                              and r.t_done <= w0)]
    faults = {rid: _faults(requests[rid]) for rid in attempted}
    failed = sorted(rid for rid, f in faults.items() if f)
    for rid in failed:
        log(f"request {rid} failed: {'; '.join(faults[rid])}")

    ctx = SimpleNamespace(
        conf=conf, cfg=cfg, mix=mix, peaks=peaks, max_batch=engine.max_batch,
        calls=calls, traced_calls=[c for c in driver.calls if c.traced],
        requests=requests, w0=w0, w1=w1, window_s=w1 - w0,
        out_tokens=out_tokens, delta=delta, spans=spans, trace=tr)
    del driver, engine, predictor, tel
    gc.collect()

    t = time.perf_counter()
    cmp = check(conf, seed, requests, w1, controls)
    phases["reference"] = time.perf_counter() - t
    nums = cmp["program"]
    checks, correct = verdict(nums, len(failed))

    if trace:
        metrics = {}
        for m in cell["per_layer"]:
            value = spec.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = {"output_tok_s": out_tokens / (w1 - w0),
                  "itl_p95_ms": (float(np.percentile(gaps, 95)) * 1e3
                                 if gaps else None),
                  "setup_s": w0 - t_start}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]
                   if values.get(m["name"]) is not None}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_modules(),
                               "idle_gaps": tr.idle_gaps()}
    result.update({
        "served_tokens_compared": nums["compared"],
        "requests_compared": len(cmp["rids"]),
        "mix_requests_finished": sum(
            1 for r in requests.values()
            if r.index > 0 and r.t_done is not None and r.t_done <= w1),
        "widest_logit_gap": nums["widest_logit_gap"],
        "off_argmax_share": nums["off_argmax"],
        "inter_token_gaps": len(gaps),
        "window_programs_traced": delta["traced"],
        "window_programs_compiled": delta["compiled"],
        "phases_s": phases})
    if controls:
        result["controls"] = {}
        for mode in controls:
            c_checks, c_correct = verdict(cmp[mode], len(failed))
            result["controls"][mode] = dict(cmp[mode], checks=c_checks,
                                            correct=c_correct)
    result["checks"] = checks
    log(f"phases (s): {phases}")
    log(f"device memory: peak {memory_peak} of {stats.get('bytes_limit')} "
        "bytes")
    log(f"window: {out_tokens} tokens, {len(gaps)} inter-token gaps, "
        f"{len(calls)} calls; programs traced {delta['traced']}, "
        f"compiled {delta['compiled']} inside it")
    log(f"compared {nums['compared']} served tokens of {len(cmp['rids'])} "
        f"requests; widest gap {nums['widest_logit_gap']}, share off the "
        f"reference's argmax {nums['off_argmax']}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result

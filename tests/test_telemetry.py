"""Runtime telemetry layer: span balance, metric registration, the
zero-overhead-off contract (token streams and deterministic engine stats
pinned identical with telemetry on vs off), the predictor scoreboard's
exact aggregation, and Chrome-trace export validated by the same checker
CI runs (``tools/check_trace.py``)."""
import glob
import os
import sys

import numpy as np
import pytest

from repro.core.metrics import (f1_over_window, prediction_hit_rate,
                                prf_from_counts)
from repro.core.policies import NextLayerAllPolicy
from repro.core.tracing import moe_layer_ids
from repro.serving.config import ServeConfig
from repro.serving.scheduler import BatchedOffloadEngine
from repro.serving import telemetry as telemetry_mod
from repro.serving.telemetry import (METRICS, NULL_TELEMETRY, PID_ENGINE,
                                     PID_REQUESTS, Telemetry)

from helpers import tiny_backbone

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _check_trace():
    sys.path.insert(0, TOOLS)
    try:
        import check_trace
    finally:
        sys.path.remove(TOOLS)
    return check_trace


# ---------------------------------------------------------------------------
# unit: spans, counters, series, off-mode
# ---------------------------------------------------------------------------

def test_span_nesting_balanced():
    tel = Telemetry()
    with tel.span(PID_ENGINE, 1, "outer"):
        with tel.span(PID_ENGINE, 1, "inner"):
            tel.instant(PID_ENGINE, 1, "tick")
    spans = tel.spans()
    names = [s.name for s in spans]
    assert names == ["outer", "inner"]  # sorted by start time
    outer, inner = spans
    assert outer.t0_s <= inner.t0_s and inner.t1_s <= outer.t1_s


def test_unbalanced_end_raises():
    tel = Telemetry()
    tel.begin(PID_ENGINE, 1, "a")
    with pytest.raises(ValueError, match="unbalanced"):
        tel.end(PID_ENGINE, 1, "b")
    tel.end(PID_ENGINE, 1, "a")  # correct close still works


def test_counters_series_and_histograms():
    tel = Telemetry()
    tel.counter("cache.hit", 2, t=0.1)
    tel.counter("cache.hit", 3, t=0.9)
    tel.counter("cache.hit", 5, t=1.1)
    assert tel.total("cache.hit") == 10
    pts = tel.series("cache.hit", 1.0)
    assert [(p.t_s, p.total, p.count) for p in pts] == [(0.0, 5, 2),
                                                        (1.0, 5, 1)]
    tel.gauge("kv.blocks_in_use", 7, t=0.2)
    tel.gauge("kv.blocks_in_use", 4, t=0.3)
    assert tel.total("kv.blocks_in_use") == 4  # last write wins
    for v in (1.0, 2.0, 3.0, 4.0):
        tel.histogram("step.wall_s", v, t=0.1)
    (h,) = tel.hist("step.wall_s")
    assert h["count"] == 4 and h["max"] == 4.0 and h["mean"] == 2.5


def test_unregistered_metric_raises():
    tel = Telemetry()
    with pytest.raises(ValueError, match="unregistered"):
        tel.counter("cache.hitz")
    assert "cache.hit" in METRICS  # the near-miss the typo was after


def test_off_mode_records_nothing_and_reuses_null_span():
    tel = Telemetry(enabled=False)
    s1, s2 = tel.span(1, 1, "a"), tel.span(2, 2, "b")
    assert s1 is s2  # shared null CM: no per-call allocation
    with s1:
        pass
    tel.counter("definitely.not.registered")  # no validation when off
    tel.begin(1, 1, "x")
    tel.end(1, 1, "mismatch-would-raise-when-on")
    tel.instant(1, 1, "i")
    tel.complete(1, 1, "c", 0.0, 1.0)
    assert tel.events() == [] and tel.spans() == []
    assert NULL_TELEMETRY.enabled is False


# ---------------------------------------------------------------------------
# f1_over_window vs the paper-era batch metrics (satellite pin)
# ---------------------------------------------------------------------------

def test_f1_over_window_matches_batch_metrics():
    rng = np.random.default_rng(0)
    predicted = [rng.choice(16, size=rng.integers(1, 8), replace=False)
                 for _ in range(20)]
    actual = [rng.choice(16, size=rng.integers(1, 8), replace=False)
              for _ in range(20)]
    w = f1_over_window(predicted, actual)
    # recall over routed experts IS the paper's prediction hit rate;
    # precision is the same quantity with the roles swapped
    assert w.recall == pytest.approx(prediction_hit_rate(predicted, actual))
    assert w.precision == pytest.approx(
        prediction_hit_rate(actual, predicted))
    # micro-F1 over the equivalent binary membership arrays
    pb = np.zeros((20, 16), bool)
    ab = np.zeros((20, 16), bool)
    for i in range(20):
        pb[i, predicted[i]] = True
        ab[i, actual[i]] = True
    tp = int((pb & ab).sum())
    fp = int((pb & ~ab).sum())
    fn = int((~pb & ab).sum())
    assert (w.tp, w.fp, w.fn) == (tp, fp, fn)
    assert w.f1 == pytest.approx(2 * tp / max(2 * tp + fp + fn, 1))
    assert (w.precision, w.recall, w.f1) == prf_from_counts(tp, fp, fn)


# ---------------------------------------------------------------------------
# engine integration: on/off parity, scoreboard, chrome export
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def backbone():
    return tiny_backbone()


PROMPTS = [[3, 17, 5, 9, 12, 7], [99, 255, 7, 42, 11, 4], [13, 5, 8, 21],
           [21, 8, 9, 77]]
MAX_NEW = 5
CACHE_LEN = 24


def _run(backbone, tel):
    cfg, model, params, _ = backbone
    n_total = len(moe_layer_ids(cfg)) * cfg.moe.num_experts
    serve = ServeConfig(max_batch=2, block_size=4, prefix_cache=True,
                        telemetry=tel)
    pol = NextLayerAllPolicy(cfg.moe.num_experts)
    eng = BatchedOffloadEngine(model, params, pol,
                               max(cfg.moe.top_k * 2, n_total // 3),
                               serve=serve)
    out = eng.generate(PROMPTS, max_new=MAX_NEW, cache_len=CACHE_LEN)
    return eng, out


@pytest.fixture(scope="module")
def on_off(backbone):
    tel = Telemetry()
    eng_on, out_on = _run(backbone, tel)
    eng_off, out_off = _run(backbone, None)
    return tel, eng_on, out_on, eng_off, out_off


def test_streams_and_stats_identical_on_off(on_off):
    """The zero-overhead contract: telemetry must be purely passive."""
    tel, eng_on, out_on, eng_off, out_off = on_off
    assert out_on == out_off
    d_on, d_off = eng_on.stats.as_dict(), eng_off.stats.as_dict()
    d_on.pop("latency"), d_off.pop("latency")  # wall-clock, may differ
    assert d_on == d_off
    assert len(tel.events()) > 0
    assert eng_off.tel is NULL_TELEMETRY and not eng_off.tel.events()


def test_request_lifecycle_spans(on_off):
    """Every admitted request gets a track with queued + request spans,
    decode step events, and a retire instant."""
    tel = on_off[0]
    spans = tel.spans()
    req_spans = [s for s in spans if s.pid == PID_REQUESTS]
    tids = {s.tid for s in req_spans}
    assert len(tids) == len(PROMPTS)  # one track per request
    for tid in tids:
        names = [s.name for s in req_spans if s.tid == tid]
        assert "request" in names and "queued" in names
        assert any(n == "decode" for n in names)
    retires = [e for e in tel.events() if e["name"] == "retire"]
    assert len(retires) == len(PROMPTS)
    # engine track carries decode_step completes and prefetch instants
    eng_names = {s.name for s in spans if s.pid == PID_ENGINE}
    assert "decode_step" in eng_names
    assert tel.total("sched.admitted") == len(PROMPTS)
    assert tel.total("sched.retired") == len(PROMPTS)


def test_scoreboard_matches_offline_recompute(on_off):
    """Per-window rows aggregate exactly to the run-level F1, and both
    match a recompute from the raw recorded series."""
    tel = on_off[0]
    sb = tel.scoreboard(bucket_s=0.05)
    assert sb["windows"], "engine run recorded no predictor windows"
    for key in ("tp", "fp", "fn", "t01_hits", "t01_misses"):
        assert sum(w[key] for w in sb["windows"]) == \
            pytest.approx(sb["total"][key])
    tp = sum(v for _, v in tel._points["predictor.tp"])
    fp = sum(v for _, v in tel._points["predictor.fp"])
    fn = sum(v for _, v in tel._points["predictor.fn"])
    assert (sb["total"]["tp"], sb["total"]["fp"], sb["total"]["fn"]) == \
        (tp, fp, fn)
    p, r, f1 = prf_from_counts(tp, fp, fn)
    assert sb["total"]["f1"] == pytest.approx(f1)
    assert sb["total"]["precision"] == pytest.approx(p)
    assert sb["total"]["recall"] == pytest.approx(r)
    for w in sb["windows"]:
        assert w["f1"] == pytest.approx(
            prf_from_counts(w["tp"], w["fp"], w["fn"])[2])
    # counter totals mirror the EngineStats the run already pins
    eng_on = on_off[1]
    assert tel.total("cache.hit") == eng_on.stats.hits
    assert tel.total("cache.miss") == eng_on.stats.misses


def test_chrome_trace_roundtrips_through_validator(on_off):
    tel = on_off[0]
    doc = tel.to_chrome_trace()
    doc["scoreboard"] = tel.scoreboard(bucket_s=0.05)
    ct = _check_trace()
    assert ct.check_artifact(doc, min_request_tracks=len(PROMPTS)) == []
    names = ct.track_names(doc["traceEvents"])
    assert "requests" in names and "engine" in names
    assert len(names["requests"]) == len(PROMPTS)


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

PROGRAM_SPANS = {"decode_step", "prefill_chunk", "store.fill",
                 "predictor.predict", "driver.route_sync",
                 "driver.logits_sync"}


def _profiled_spans(backbone, tmp_path):
    """(name, start_ns, end_ns) of the program's spans in a CPU profiler
    trace of one telemetry-on run, read back from the trace file."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        _run(backbone, Telemetry())
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in PROGRAM_SPANS]


def test_live_spans_land_in_the_profiler_trace_nested(backbone, tmp_path):
    """Each live span is also a profiler annotation: the decode step holds
    the fills, the prediction and both syncs, and any two program spans
    either nest or do not meet."""
    spans = _profiled_spans(backbone, tmp_path)
    steps = [(a, b) for n, a, b in spans if n == "decode_step"]
    assert steps and any(n == "prefill_chunk" for n, _, _ in spans)
    for name in ("store.fill", "predictor.predict", "driver.route_sync",
                 "driver.logits_sync"):
        assert any(sa <= a and b <= sb for n, a, b in spans if n == name
                   for sa, sb in steps), name
    spans.sort(key=lambda s: (s[1], -s[2]))
    for i, (_, a, b) in enumerate(spans):
        for _, c, d in spans[i + 1:]:
            if c >= b:
                break
            assert d <= b, "program spans overlap without nesting"


def test_off_mode_builds_no_trace_annotation(backbone, monkeypatch):
    """A whole telemetry-off run constructs no profiler annotation; the
    counting stand-in does see a live span's."""
    import jax
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(telemetry_mod, "TraceAnnotation", Counting)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    _run(backbone, None)
    assert built == []
    with Telemetry().span(PID_ENGINE, 1, "x"):
        pass
    assert built == ["x"]


def test_validator_catches_broken_traces():
    ct = _check_trace()
    tel = Telemetry()
    with tel.span(PID_ENGINE, 1, "ok"):
        pass
    good = tel.to_chrome_trace()
    assert ct.check_artifact(good) == []
    # unbalanced: drop the E event
    bad = {"traceEvents": [e for e in good["traceEvents"]
                           if e["ph"] != "E"]}
    assert any("never closed" in p for p in ct.check_artifact(bad))
    # non-monotonic ts on one track
    ooo = {"traceEvents": list(good["traceEvents"]) + [
        {"name": "late", "ph": "i", "pid": PID_ENGINE, "tid": 1,
         "ts": -1.0, "s": "t"}]}
    assert any("ts" in p for p in ct.check_artifact(ooo))
    # unnamed track
    anon = {"traceEvents": [
        {"name": "x", "ph": "i", "pid": 9, "tid": 9, "ts": 0.0, "s": "t"}]}
    assert any("process_name" in p for p in ct.check_artifact(anon))
    # scoreboard whose windows don't sum to the total
    lying = dict(good)
    lying["scoreboard"] = {
        "windows": [{"tp": 1, "fp": 0, "fn": 0, "f1": 1.0,
                     "t01_hits": 0, "t01_misses": 0}],
        "total": {"tp": 2, "fp": 0, "fn": 0, "f1": 1.0,
                  "t01_hits": 0, "t01_misses": 0}}
    assert any("windows sum" in p for p in ct.check_artifact(lying))

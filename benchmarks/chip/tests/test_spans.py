"""The split of the device's idle time by the program's spans, on small
synthetic traces: each reader against hand-computed values, and the five
idle shares against ``device.idle_share``."""
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import spec
from benchmarks.chip.spans import PROGRAM_SPANS, idle_ns
from benchmarks.chip.trace import Trace

MS = 1e6  # nanoseconds
IDLE_SHARES = ("store.fill_idle_pct", "predictor.idle_pct",
               "driver.sync_idle_pct", "driver.host_idle_pct",
               "device.unattributed_idle_pct")
SPAN_READERS = IDLE_SHARES + ("store.fill_ms", "predictor.predict_ms")


def read(name, trace):
    return spec.reader(name).read(SimpleNamespace(trace=trace))


def synthetic():
    """20 ms window; the device busy 0-2, 8-9 and 15-16 ms, so idle 2-8,
    9-15 and 16-20 ms (16 ms). A decode step (1.5-11.5 ms) holds two
    fills, a routing sync, a prediction and a logits sync; a prefill chunk
    (13-18 ms) holds a third fill; the harness's own span and a prediction
    that ends after the window are no program spans of the window."""
    ops = {0: [("fusion.1", 0 * MS, 2 * MS), ("fusion.2", 8 * MS, 9 * MS),
               ("fusion.3", 15 * MS, 16 * MS)]}
    host = [("bench.traced", 0 * MS, 20 * MS),
            ("bench.decode_step", 1 * MS, 12 * MS),
            ("decode_step", 1.5 * MS, 11.5 * MS),
            ("store.fill", 2.5 * MS, 4.5 * MS),
            ("DevicePut", 3 * MS, 4 * MS),
            ("store.fill", 5 * MS, 6 * MS),
            ("driver.route_sync", 6 * MS, 8.5 * MS),
            ("predictor.predict", 9.5 * MS, 10.5 * MS),
            ("driver.logits_sync", 11 * MS, 11.5 * MS),
            ("prefill_chunk", 13 * MS, 18 * MS),
            ("store.fill", 14 * MS, 14.5 * MS),
            ("predictor.predict", 19.5 * MS, 24 * MS)]
    return Trace(ops, {}, host)


def test_idle_time_goes_to_the_innermost_program_span():
    by_span = {k: v / MS for k, v in idle_ns(synthetic()).items()}
    assert by_span == pytest.approx({
        "store.fill": 2.0 + 1.0 + 0.5,         # a fill in a step or chunk
        "driver.route_sync": 2.0,              # idle 6-8 ms
        "driver.logits_sync": 0.5,
        "predictor.predict": 1.0 + 0.5,        # 9.5-10.5 and 19.5-20 ms
        "decode_step": 0.5 + 0.5 + 0.5 + 0.5,  # between its inner spans
        "prefill_chunk": 1.5 + 2.0,
        None: 1.5 + 1.5})                      # 11.5-13 and 18-19.5 ms


def test_each_reader_by_hand():
    t = synthetic()
    expected = {"store.fill_idle_pct": 17.5, "predictor.idle_pct": 7.5,
                "driver.sync_idle_pct": 12.5, "driver.host_idle_pct": 27.5,
                "device.unattributed_idle_pct": 15.0,
                # fills of 2, 1 and 0.5 ms; the prediction that ends after
                # the window is not counted
                "store.fill_ms": 1.0, "predictor.predict_ms": 1.0}
    assert {n: read(n, t) for n in SPAN_READERS} == pytest.approx(expected)


def test_the_five_idle_shares_add_up_to_the_idle_share():
    t = synthetic()
    assert sum(read(n, t) for n in IDLE_SHARES) == pytest.approx(
        read("device.idle_share", t)) == pytest.approx(80.0)


def random_trace(rng):
    """A window of 400 ticks of 1000 ns: ops at random, and program spans
    nested as one thread's calls nest, some crossing the window's edges."""
    tick = 1000.0
    ops = []
    for a in rng.integers(-20, 420, size=30):
        ops.append(("op", a * tick, (a + rng.integers(1, 15)) * tick))
    host = [("bench.traced", 0.0, 400 * tick)]

    def nest(a, b, depth):
        t = a
        while t < b - 2 and depth < 4:
            s = int(rng.integers(t, b - 1))
            e = int(rng.integers(s + 1, min(b, s + 60) + 1))
            if rng.random() < 0.7:
                name = str(rng.choice(PROGRAM_SPANS))
            else:
                name = "bench.other"
            host.append((name, s * tick, e * tick))
            nest(s, e, depth + 1)
            t = e + int(rng.integers(0, 20))

    nest(-30, 430, 0)
    return Trace({0: ops}, {}, host), tick


def brute_force(trace, tick):
    """Tick by tick: idle ticks by the innermost program span."""
    out = {}
    for i in range(400):
        t0, t1 = i * tick, (i + 1) * tick
        if any(a < t1 and b > t0 for _, a, b in trace.ops[0]):
            continue
        inner = [(a, -b, n) for n, a, b in trace.host
                 if n in PROGRAM_SPANS and a <= t0 and b >= t1]
        label = max(inner)[2] if inner else None
        out[label] = out.get(label, 0.0) + tick
    return out


@pytest.mark.parametrize("seed", range(6))
def test_the_split_matches_a_tick_by_tick_count(seed):
    trace, tick = random_trace(np.random.default_rng(seed))
    by_span = idle_ns(trace)
    want = brute_force(trace, tick)
    assert {k: v for k, v in by_span.items() if v} == pytest.approx(want)
    assert sum(read(n, trace) for n in IDLE_SHARES) == pytest.approx(
        read("device.idle_share", trace))


def test_a_program_without_its_spans_gives_nothing_to_read():
    """The parent program records no program spans: every reader here
    returns None, and none raises."""
    t = synthetic()
    t.host = [h for h in t.host if h[0] not in PROGRAM_SPANS]
    for name in SPAN_READERS:
        assert read(name, t) is None
        assert read(name, None) is None

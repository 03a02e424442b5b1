"""The device's idle time, split by the program's own spans.

The program records its spans in ``serving/telemetry.py``, and each live
span there is also a ``jax.profiler.TraceAnnotation`` of the same name. In
a traced run they lie on the host line that holds ``bench.traced``
(``Trace.host``), on the clock of the device's ops. Each idle nanosecond of
the first device inside the window goes to the innermost program span open
at that instant, or to none. A program that records none of these spans
gives nothing to read, and every reader here returns None.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from benchmarks.chip.trace import gaps

PROGRAM_SPANS = ("decode_step", "prefill_chunk", "store.fill",
                 "predictor.predict", "driver.route_sync",
                 "driver.logits_sync")


def idle_ns(trace) -> Optional[Dict[Optional[str], float]]:
    """Idle nanoseconds of the first device in the window, by the name of
    the innermost program span around them (``None``: under no program
    span); None where the trace holds no program span."""
    spans = sorted((a, -b, name) for name, a, b in trace.host
                   if name in PROGRAM_SPANS)
    if not spans:
        return None
    lo, hi = trace.lo, trace.hi
    cuts = sorted({lo, hi} | {t for a, nb, _ in spans for t in (a, -nb)
                              if lo < t < hi})
    idle = gaps(trace.busy(min(trace.ops)), lo, hi)
    out: Dict[Optional[str], float] = {}
    stack, i, g = [], 0, 0
    for a, b in zip(cuts, cuts[1:]):
        # spans of one thread nest: the innermost open at ``a`` is the
        # last one opened that has not yet closed
        while i < len(spans) and spans[i][0] <= a:
            s, nb, name = spans[i]
            while stack and stack[-1][0] <= s:
                stack.pop()
            stack.append((-nb, name))
            i += 1
        while stack and stack[-1][0] <= a:
            stack.pop()
        while g < len(idle) and idle[g][1] <= a:
            g += 1
        k, ns = g, 0.0
        while k < len(idle) and idle[k][0] < b:
            ns += min(b, idle[k][1]) - max(a, idle[k][0])
            k += 1
        if ns:
            label = stack[-1][1] if stack else None
            out[label] = out.get(label, 0.0) + ns
    return out


def idle_pct(ctx, *names) -> Optional[float]:
    """Idle time under the named program spans (``None`` for none), as a
    percentage of the traced window."""
    if ctx.trace is None or ctx.trace.hi <= ctx.trace.lo:
        return None
    by_span = idle_ns(ctx.trace)
    if by_span is None:
        return None
    return 100.0 * sum(by_span.get(n, 0.0) for n in names) / (
        ctx.trace.hi - ctx.trace.lo)


def median_ms(ctx, name: str) -> Optional[float]:
    """Median duration of the named program span among those that end in
    the traced window, on the profiler's clock."""
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.lo, ctx.trace.hi
    durs = [b - a for n, a, b in ctx.trace.host
            if n == name and lo < b <= hi]
    return float(np.median(durs)) / 1e6 if durs else None

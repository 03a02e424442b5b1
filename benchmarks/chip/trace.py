"""Reduction of a JAX profiler trace to the numbers the metrics read.

The profiler writes one ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it with nothing but JAX. Device planes (``/device:TPU:<n>``) hold a line of
XLA modules (one event per jitted program run) and a line of XLA ops. Times are
nanoseconds on the trace's clock; the window is the ``bench.traced``
annotation that spans the traced part of the run. Host spans are those of
the thread that opened it: the benchmark's ``bench.*`` annotations and the
runtime's own (a program's dispatch, a host-to-device copy, a wait for a
result), which name what the host was doing while the device sat idle.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]
WINDOW = "bench.traced"


def module_name(name: str) -> str:
    """``jit_expert_from_slots(123)`` -> ``expert_from_slots``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals clipped to [lo, hi], in order."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """Events of one trace: ``ops`` and ``modules`` per device as
    (name, start, end) lists, ``host`` spans as (name, start, end)."""

    def __init__(self, ops: Dict[int, list], modules: Dict[int, list],
                 host: list):
        self.ops = ops
        self.modules = modules
        self.host = host
        spans = [(a, b) for n, a, b in host if n == WINDOW]
        if len(spans) != 1:
            raise ValueError(f"{len(spans)} '{WINDOW}' spans in the trace")
        self.lo, self.hi = spans[0]

    @classmethod
    def load(cls, directory: str) -> "Trace":
        from jax.profiler import ProfileData
        paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise ValueError(f"{len(paths)} xplane files under {directory}")
        data = ProfileData.from_file(paths[0])
        ops, modules, host = {}, {}, []
        for plane in data.planes:
            m = re.match(r"/device:TPU:(\d+)", plane.name)
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.end_ns))
                       for e in line.events]
                if m and line.name == "XLA Ops":
                    ops[int(m.group(1))] = evs
                elif m and line.name == "XLA Modules":
                    modules[int(m.group(1))] = evs
                elif (plane.name.startswith("/host:")
                      and any(n == WINDOW for n, _, _ in evs)):
                    host = evs
        if not ops:
            raise ValueError("the trace holds no device ops")
        return cls(ops, modules, host)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy(self, device: int) -> List[Interval]:
        return union(((a, b) for _, a, b in self.ops[device]),
                     self.lo, self.hi)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        tot = [sum(b - a for a, b in self.busy(d)) for d in self.ops]
        return sum(tot) / len(tot) / 1e9

    def module_s(self, names: Sequence[str]) -> float:
        """Device seconds of the named programs, summed over devices."""
        return sum(min(b, self.hi) - max(a, self.lo)
                   for evs in self.modules.values() for n, a, b in evs
                   if module_name(n) in names and b > self.lo
                   and a < self.hi) / 1e9

    def op_s(self, pattern: str, modules: Sequence[str]) -> float:
        """Device seconds of ops whose name matches ``pattern`` and that
        run inside one of the named programs."""
        rx = re.compile(pattern)
        total = 0.0
        for dev, evs in self.ops.items():
            mods = sorted((a, b) for n, a, b in self.modules.get(dev, [])
                          if module_name(n) in modules)
            starts = [a for a, _ in mods]
            for n, a, b in evs:
                if not rx.search(n) or b <= self.lo or a >= self.hi:
                    continue
                i = bisect.bisect_right(starts, a) - 1
                if i >= 0 and mods[i][1] >= b:
                    total += min(b, self.hi) - max(a, self.lo)
        return total / 1e9

    def top_modules(self, n: int = 10) -> List[list]:
        acc: Dict[str, float] = {}
        for evs in self.modules.values():
            for name, a, b in evs:
                if b > self.lo and a < self.hi:
                    k = module_name(name)
                    acc[k] = acc.get(k, 0.0) + (min(b, self.hi)
                                                - max(a, self.lo)) / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle seconds of the first device, summed by what the host was
        doing in the middle of each idle stretch (the innermost host span
        there), largest first."""
        dev = min(self.ops)
        found = gaps(self.busy(dev), self.lo, self.hi)
        mids = sorted(((a + b) / 2, b - a) for a, b in found)
        acc: Dict[str, float] = {}
        for (_, dur), name in zip(mids, self.labels([t for t, _ in mids])):
            acc[name] = acc.get(name, 0.0) + dur / 1e9
        return [[k, v] for k, v in sorted(acc.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def labels(self, times: Sequence[float]) -> List[str]:
        """For ascending times, the innermost host span around each (spans
        of one thread nest), or ``bench.between_calls``."""
        spans = sorted((a, -b, name) for name, a, b in self.host
                       if name != WINDOW)
        out, stack, i = [], [], 0
        for t in times:
            while i < len(spans) and spans[i][0] <= t:
                a, nb, name = spans[i]
                while stack and stack[-1][0] <= a:
                    stack.pop()
                stack.append((-nb, name))
                i += 1
            while stack and stack[-1][0] <= t:
                stack.pop()
            out.append(stack[-1][1] if stack else "bench.between_calls")
        return out

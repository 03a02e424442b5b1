"""Decode driver: share of the traced window in which the device sat idle
while the innermost program span was ``decode_step`` or ``prefill_chunk``
itself: the driver's own Python (prefetch planning, pinning, slot tables,
the predictor's ``observe_batch``, dispatch)."""
from benchmarks.chip.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "decode_step", "prefill_chunk")

"""Predictor: share of the traced window in which the device sat idle
while the innermost program span was ``predictor.predict``."""
from benchmarks.chip.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "predictor.predict")

"""Predictor (core/policies.py, called from DecodeCore._submit_prefetch):
median time of one prediction, its feature assembly, the jitted
``predictor_forward`` and the host's read of its output, from the
program's ``predictor.predict`` spans on the profiler's clock."""
from benchmarks.chip.spans import median_ms


def read(ctx):
    return median_ms(ctx, "predictor.predict")

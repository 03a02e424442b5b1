"""Decode driver (serving/engine.py DecodeCore.step): median wall time of
a decode step in the window, from the program's ``decode_step`` telemetry
spans. The step ends when the host holds its logits, so the device has
finished."""
import numpy as np


def read(ctx):
    walls = ctx.spans.get("decode_step")
    return float(np.median(walls)) * 1e3 if walls else None

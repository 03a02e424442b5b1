"""Decode driver (serving/engine.py DecodeCore.prefill_chunk): median wall
time of one chunked-prefill call in the window, from the program's
``prefill_chunk`` telemetry spans (host logits in hand, so synced)."""
import numpy as np


def read(ctx):
    walls = ctx.spans.get("prefill_chunk")
    return float(np.median(walls)) * 1e3 if walls else None

"""Predictor (core/policies.py OnlineMoEBeyondPolicy): share of the
experts predicted for the next MoE layer that its router then used, from
the program's scoreboard counters (``predictor.tp`` and ``predictor.fp``)
over the window."""


def read(ctx):
    tp, fp = ctx.delta.get("predictor.tp", 0), ctx.delta.get("predictor.fp", 0)
    return tp / (tp + fp) if tp + fp else None

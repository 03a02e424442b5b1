"""Decode driver (serving/engine.py DecodeCore): share of the traced window
in which the device sat idle while the host waited for the router's
expert ids (``driver.route_sync``) or for the logits
(``driver.logits_sync``)."""
from benchmarks.chip.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "driver.route_sync", "driver.logits_sync")

"""Device: share of the traced window in which the device sat idle under
no program span: the scheduler, the harness's bookkeeping, and whatever
the program's spans do not cover. With the four other ``*idle_pct``
metrics it adds up to ``device.idle_share``."""
from benchmarks.chip.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, None)

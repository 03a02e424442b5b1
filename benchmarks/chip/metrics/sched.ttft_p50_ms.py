"""Scheduler (serving/scheduler.py): median time from a request's
submission to its first served token, over the requests submitted inside
the window whose first token came before it closed (host clock)."""
import numpy as np


def read(ctx):
    ttft = [r.served[0][2] - r.t_submit for r in ctx.requests.values()
            if ctx.w0 < r.t_submit <= ctx.w1 and r.served
            and r.served[0][2] <= ctx.w1]
    return float(np.median(ttft)) * 1e3 if ttft else None

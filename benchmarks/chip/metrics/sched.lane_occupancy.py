"""Scheduler (serving/scheduler.py): busy lanes over ``max_batch``, per
decode step of the window, averaged. A lane is busy in a step when its
request decodes in it or ran a prefill chunk since the previous step."""


def read(ctx):
    ratios, prefilling = [], set()
    for c in ctx.calls:
        if c.kind == "prefill":
            prefilling.update(c.rids)
            continue
        busy = len(c.rids) + len(prefilling - set(c.rids))
        ratios.append(busy / ctx.max_batch)
        prefilling = set()
    return sum(ratios) / len(ratios) if ratios else None

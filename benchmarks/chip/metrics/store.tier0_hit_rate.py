"""Expert store (serving/offload.py): share of demanded experts already in
the tier-0 slot buffer, from the program's ``cache.hit`` and ``cache.miss``
counters over the window."""


def read(ctx):
    hit, miss = ctx.delta.get("cache.hit", 0), ctx.delta.get("cache.miss", 0)
    return hit / (hit + miss) if hit + miss else None

"""Expert store (serving/offload.py): expert weight megabytes copied host
to device per output token served in the window (the engine's
``fetch_bytes`` count)."""


def read(ctx):
    if not ctx.out_tokens:
        return None
    return ctx.delta["fetch_bytes"] / 1e6 / ctx.out_tokens

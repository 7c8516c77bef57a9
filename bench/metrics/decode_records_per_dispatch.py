"""Records the background bulk decoder took per fused dispatch in the
traced run: the service's own ``decoded_records / decode_dispatches``."""


def read(ctx):
    n = ctx.obs["decode_dispatches"]
    return ctx.obs["decoded_records"] / n if n else None

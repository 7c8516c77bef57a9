"""Whole-step model FLOP/s utilisation of the population round: the
model operations of each finished client (``work.client_ops``: fine-tune
forward and backward, the encoder pass, the encode) times clients per
second of the traced window, over chips times the chip's bf16 peak."""


def read(ctx):
    if not ctx.obs["clients"]:
        return None
    rate = ctx.obs["clients"] / ctx.obs["elapsed_s"]
    return 100.0 * rate * ctx.obs["client_ops"] / (
        ctx.chips * ctx.peak["flops_per_s"])

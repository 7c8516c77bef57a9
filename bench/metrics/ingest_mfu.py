"""The whole ingest path's share of the chip's peak in the traced run:
the least time the chip needs for every record the window decoded
(``work.decode_bytes`` per record over the chip's bandwidth: a gather
does no arithmetic, so bandwidth is the peak that bounds it), over the
window's seconds (drain included) times the chips."""


def read(ctx):
    n = ctx.obs["decoded_records"]
    if not n:
        return None
    nbytes = n * ctx.work.decode_bytes(ctx.model, ctx.obs["record_positions"])
    return 100.0 * nbytes / ctx.peak["bytes_per_s"] / (
        ctx.obs["elapsed_s"] * ctx.chips)

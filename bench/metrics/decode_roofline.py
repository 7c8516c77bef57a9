"""Share of its roofline that the fused decode kernel reaches: the bytes
the window's decodes must move (``work.decode_bytes`` per record: packed
codes and table in, float32 feature rows out) over the chip's bandwidth,
over the kernel's device time in the trace. A gather does no arithmetic,
so bandwidth bounds it. The kernel's trace events are named after its
Pallas wrapper (``%decode_codes_pallas.<n> = ... custom-call``)."""

KERNELS = ("%decode_codes_pallas",)


def read(ctx):
    t = ctx.trace.kernel_seconds(KERNELS[0])
    if t is None:
        return None
    records = ctx.obs["decoded_records"] / ctx.chips
    nbytes = records * ctx.work.decode_bytes(ctx.model,
                                             ctx.obs["record_positions"])
    return 100.0 * nbytes / ctx.peak["bytes_per_s"] / t

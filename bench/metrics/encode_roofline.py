"""Share of its roofline that the fused encode kernel reaches: the least
time the chip needs for the window's encodes (``work.encode_ops`` and
``work.encode_bytes`` per client record, the larger of operations over
peak and bytes over bandwidth) over the kernel's device time in the
trace, per device. The kernel's trace events are named after its Pallas
wrapper (``%encode_codes_pallas.<n> = ... custom-call``)."""

KERNELS = ("%encode_codes_pallas",)


def read(ctx):
    t = ctx.trace.kernel_seconds(KERNELS[0])
    if t is None:
        return None
    n, w = ctx.obs["record_positions"], ctx.work
    records = ctx.obs["encoded_records"] / ctx.chips
    least, _ = w.roofline_seconds(records * w.encode_ops(ctx.model, n),
                                  records * w.encode_bytes(ctx.model, n),
                                  ctx.peak)
    return 100.0 * least / t

"""Share of the fused-sampling kernel's device time that the least
sampling work needs: each call reads its rows' f32 logits over the
vocabulary once (``flops.sampling``), at the chip's HBM bandwidth, over
the summed self time of the kernel's calls in the traced stretch."""

import flops

KERNEL = r"^fused_sample$"      # the Pallas kernel's custom call
ROW_GROUP = 8                   # the kernel pads its rows to 8


def read(ctx):
    if ctx.trace is None:
        return None
    secs, n = ctx.trace.op_seconds(KERNEL)
    if n == 0 or secs <= 0:
        return None
    slots = ctx.slots // ctx.chips
    rows = -(-slots // ROW_GROUP) * ROW_GROUP
    least = n * flops.sampling(ctx.dims, rows) / ctx.peaks.hbm_bw
    return 100.0 * least / secs

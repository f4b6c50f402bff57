"""Model FLOP/s utilization of the whole serving step: the FLOPs that the
traced stretch's prefills and decode tokens need, as the cell's model
family counts them (``ctx.work``, attention at the actual lengths), over
the traced window times the chips times the chip's peak."""


def read(ctx):
    if (ctx.trace is None or ctx.stretch is None or ctx.trace.window_s <= 0
            or ctx.trace.busy_s <= 0):
        return None
    count = ctx.work(ctx.stretch)
    return 100.0 * (count.decode[0] + count.prefill[0]) / (
        ctx.trace.window_s * ctx.chips * ctx.peaks.flops)

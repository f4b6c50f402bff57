"""Share of the decode megasteps' device time that the least decode work
needs: the cell's model family counts it (``ctx.work``, for a dense
block every weight read once per step and each token's K/V at its own
context length), at the chip's peaks, over the device time of the
megastep programs in the traced stretch."""

import flops

MEGASTEP = r"_mega"     # the engine's jitted megastep function


def read(ctx):
    if ctx.trace is None or ctx.stretch is None:
        return None
    secs, n = ctx.trace.program_seconds(MEGASTEP)
    if n == 0 or secs <= 0 or ctx.stretch.counters["decode_steps"] <= 0:
        return None
    f, b = ctx.work(ctx.stretch).decode
    least = flops.least_seconds(f, b, ctx.peaks.flops, ctx.peaks.hbm_bw)
    return 100.0 * least / secs

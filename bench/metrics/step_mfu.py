"""Model FLOP/s utilization of the whole serving step: the FLOPs that the
traced stretch's prefills and decode tokens need (``flops.prefill`` and
``flops.decode``, attention at the actual lengths), over the traced
window times the chips times the chip's peak."""

import flops


def read(ctx):
    if (ctx.trace is None or ctx.stretch is None or ctx.trace.window_s <= 0
            or ctx.trace.busy_s <= 0):
        return None
    st = ctx.stretch
    fd, _ = flops.decode(ctx.dims, int(st.counters["decode_steps"]),
                         st.decode_tokens, st.context_sum)
    fp, _ = flops.prefill(ctx.dims, st.prefill_lens, 0)
    return 100.0 * (fd + fp) / (ctx.trace.window_s * ctx.chips
                                * ctx.peaks.flops)

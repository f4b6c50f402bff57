"""Device milliseconds per decode step: device time of the fused decode
megastep programs in the traced stretch over the decode steps run."""

MEGASTEP = r"_mega"     # the engine's jitted megastep function


def read(ctx):
    if ctx.trace is None or ctx.stretch is None:
        return None
    secs, n = ctx.trace.program_seconds(MEGASTEP)
    steps = ctx.stretch.counters["decode_steps"]
    if n == 0 or steps <= 0:
        return None
    return 1e3 * secs / steps

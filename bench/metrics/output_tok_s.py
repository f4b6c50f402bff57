"""Output tokens per second per chip: every output token the window
produced, over the window's wall time, divided by the chips used."""


def read(ctx):
    return ctx.tally.tokens / ctx.window_s / ctx.chips

"""Share of decode slots that produced a token: decode-step tokens over
decode steps times the slots of one engine, over the window."""


def read(ctx):
    steps = ctx.counters["decode_steps"]
    if steps <= 0:
        return None
    return 100.0 * ctx.tally.decode_tokens / (steps * ctx.slots / ctx.chips)

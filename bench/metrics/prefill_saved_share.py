"""Share of prompt tokens served from shared KV (fork siblings and
prefix-index hits) instead of being forwarded, over the window."""


def read(ctx):
    saved = ctx.counters["prefill_tokens_saved"]
    total = ctx.counters["prefill_tokens"] + saved
    if total <= 0:
        return None
    return 100.0 * saved / total

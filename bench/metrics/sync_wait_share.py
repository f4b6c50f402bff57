"""Share of the window the host spent blocked landing staged KV blobs in
the host store (the engine's ``sync_wait_s``, host wall time around the
blocking device-to-host copy)."""


def read(ctx):
    return 100.0 * ctx.counters["sync_wait_s"] / ctx.window_s

"""Share of the traced stretch the host spent building executables: the
first call of each one the program's jit caches made anew (its
``engine.node.compile`` spans: trace, lower, compile or load from the
compilation cache).  0.0 when the warm-up met every executable; nothing
without the program's ``engine.node.*`` spans."""

import idle_spans


def read(ctx):
    s = idle_spans.of(ctx)
    if s is None or s.window_s <= 0 or not s.has("engine.node."):
        return None
    return 100.0 * s.span_s.get("engine.node.compile", 0.0) / s.window_s

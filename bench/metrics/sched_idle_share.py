"""Share of the traced stretch in which the device was idle while the
host ran the scheduler's own code: idle time whose innermost host span
is one of the program's ``engine.sched.*`` (a round, an event handler)
or ``engine.prim.*`` (a coroutine primitive) spans, over the stretch.
Nothing without the program's ``engine.sched.*`` spans."""

import idle_spans


def read(ctx):
    s = idle_spans.of(ctx)
    if s is None or s.window_s <= 0 or not s.has("engine.sched."):
        return None
    return 100.0 * s.idle_under("engine.sched.", "engine.prim.") / s.window_s

"""Share of the traced stretch the host spent writing staged slot
installs to the device (the program's ``engine.node.install`` spans:
packing, pad and stack to full slots, the copy to the device and the
scatter's dispatch).  Nothing without the program's ``engine.node.*``
spans."""

import idle_spans


def read(ctx):
    s = idle_spans.of(ctx)
    if s is None or s.window_s <= 0 or not s.has("engine.node."):
        return None
    return 100.0 * s.span_s.get("engine.node.install", 0.0) / s.window_s

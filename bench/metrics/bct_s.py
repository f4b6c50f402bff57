"""Batch completion time: the window's wall time over the whole jobs it
ran back to back (a job ends with its slowest request)."""


def read(ctx):
    if ctx.tally.jobs == 0:
        return None
    return ctx.window_s / ctx.tally.jobs

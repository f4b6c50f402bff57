"""Set-up: process start to the start of the measured window (loading,
weights, warm-up and any compilation), on the host clock."""


def read(ctx):
    return ctx.setup_s

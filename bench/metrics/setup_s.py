"""setup_s: process start to the opening of the measured window (s)."""


def read(ctx):
    return ctx.setup_s

"""Seconds from process start to the first timed frame: imports, the
traffic, compiles or cache loads, and the warm-up round (host clock)."""


def read(ctx):
    return ctx.setup_s

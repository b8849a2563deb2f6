"""Images delivered in the window over the window's seconds (host clock)."""


def read(ctx):
    return ctx.frames / ctx.window_s if ctx.window_s > 0 else None

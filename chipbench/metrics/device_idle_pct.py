"""Share of the traced window in which no operation ran on a chip,
averaged over the cell's chips."""

from chipbench.stats import busy_s, traced_window_s


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - busy_s(ctx.trace) / traced_window_s(ctx.trace))

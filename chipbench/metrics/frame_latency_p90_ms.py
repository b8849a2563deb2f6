"""90th percentile latency of all frames of the window (host clock).
Listed only for cells whose window holds 100 frames or more, so that
ten or more samples lie beyond it."""

from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.latencies, 90)

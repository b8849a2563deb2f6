"""Median latency of all frames of the window, from the hand-over to the
entry to the image on the host (host clock)."""

from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.latencies, 50)

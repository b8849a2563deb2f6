"""Median of the program's own step timer over the window:
``StreamScheduler.tick_ms`` (service) or ``LatencyReport.frame_ms``
(stream)."""

from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.step_ms, 50)

"""Collective device time per frame during which no other operation ran
on that chip, averaged over the cell's chips (device trace)."""

from chipbench import trace as tr
from chipbench.stats import exposed_collective_s


def read(ctx):
    if ctx.trace is None or not ctx.frames or not any(
            tr.is_collective(e.code)
            for evs in ctx.trace.ops.values() for e in evs):
        return None
    devs = list(ctx.trace.ops)
    total = sum(exposed_collective_s(ctx.trace, d) for d in devs) / len(devs)
    return 1e3 * total / ctx.frames

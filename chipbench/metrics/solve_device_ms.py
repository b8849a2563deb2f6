"""Device time of the programs the window runs, per served frame,
averaged over the cell's chips: every program run (``XLA Modules`` on a
TPU) inside the traced window is summed, so that a frame program split
into several programs still counts whole."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.frames or not t.modules:
        return None
    lo, hi = t.window()
    total = 0.0
    for evs in t.modules.values():
        for e in evs:
            s, f = max(e.start, lo), min(e.end, hi)
            if f > s:
                total += f - s
    return total / len(t.modules) / 1e6 / ctx.frames

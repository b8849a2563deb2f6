"""Least time of one frame's algorithmic work on the cell's chips (the
problem's ``frame_least_seconds``: for NLINV, the larger of operations
over peak and least bytes over bandwidth, from ``chipbench/work/nlinv.py``,
with the fewest CG iterations the plain reference ran for a compared
frame), over the window's wall time per frame."""


def read(ctx):
    if not ctx.frames:
        return None
    least = ctx.problem.frame_least_seconds(ctx.cell.cfg, ctx.cg_iters,
                                            ctx.peak, ctx.chips)
    return 100.0 * least / (ctx.window_s / ctx.frames)

"""Device time per frame and chip of the 2-D DFT kernel (operations
named ``dft2c_pallas.N``) inside the traced window (device trace).
Nothing is read where no such kernel ran: a program whose transforms are
XLA's own FFT."""

from chipbench import trace as tr
from chipbench.stats import op_seconds

KERNEL = "dft2c_pallas"


def read(ctx):
    if ctx.trace is None or not ctx.frames or not ctx.trace.ops:
        return None
    secs = op_seconds(ctx.trace, lambda e: tr.opcode(e.name)).get(KERNEL)
    if not secs:
        return None
    return 1e3 * secs / len(ctx.trace.ops) / ctx.frames

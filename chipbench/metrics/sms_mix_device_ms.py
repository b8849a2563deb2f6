"""Device time per frame and chip of the SMS partition-mixing kernels
(operations named ``sms_lincomb_pallas.N`` and ``sms_adjoint_pallas.N``)
inside the traced window (device trace).  Nothing is read where no such
kernel ran."""

from chipbench import trace as tr
from chipbench.stats import op_seconds

KERNELS = ("sms_lincomb_pallas", "sms_adjoint_pallas")


def mix_seconds(trace) -> dict:
    """Device seconds (summed over chips) of each mixing kernel."""
    by = op_seconds(trace, lambda e: tr.opcode(e.name))
    return {k: by[k] for k in KERNELS if by.get(k)}


def read(ctx):
    if ctx.trace is None or not ctx.frames or not ctx.trace.ops:
        return None
    secs = mix_seconds(ctx.trace)
    if not secs:
        return None
    return 1e3 * sum(secs.values()) / len(ctx.trace.ops) / ctx.frames

"""Share of the HBM roofline the SMS partition-mixing kernels reach: the
least bytes of their calls in the traced window (the coil stacks each
call streams, ``chipbench/work/sms_nlinv.py``, times the calls the trace
counts; G's one-term calls are ``newton`` per frame) over their device
time times the peak bandwidth.  Nothing is read where no such kernel
ran."""

from chipbench import trace as tr
from chipbench.metrics.sms_mix_device_ms import KERNELS, mix_seconds
from chipbench.work.sms_nlinv import mix_bytes


def _calls(trace, lo, hi) -> dict:
    out = dict.fromkeys(KERNELS, 0)
    for evs in trace.ops.values():
        for e in evs:
            k = tr.opcode(e.name)
            if k in out and lo <= e.start < hi:
                out[k] += 1
    return out


def read(ctx):
    if ctx.trace is None or not ctx.frames or not ctx.trace.ops:
        return None
    secs = mix_seconds(ctx.trace)
    if len(secs) != len(KERNELS):
        return None
    calls = _calls(ctx.trace, *ctx.trace.window())
    chips = len(ctx.trace.ops)
    one_term = int(ctx.cell.cfg["newton"]) * ctx.frames * chips
    if calls[KERNELS[0]] < one_term:
        return None
    nbytes = mix_bytes(ctx.cell.cfg, one_term, calls[KERNELS[0]],
                       calls[KERNELS[1]], ctx.chips)
    return 100.0 * nbytes / (sum(secs.values())
                             * ctx.peak["hbm_bytes_per_s"])

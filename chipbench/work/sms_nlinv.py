"""Least work of one SMS-NLINV frame, counted from its shapes.

The FFTs follow ``chipbench/work/nlinv.py``: a frame of ``newton`` steps
that run ``cg_total`` CG iterations in all runs ``4 newton + 4 cg_total
+ 1`` batches of 2-D FFTs, each batch now M*J planes (every slice's
coils), 5 N log2 N operations and one read and one write per plane.

The partition mixing couples the M planes of one channel, so it cannot
ride along inside a per-plane FFT pass: its least bytes are counted on
their own, as the coil stacks (M*J planes, complex64) that the two fused
kernels stream, each read once:

- ``sms_lincomb``, the forward chain, reads one stack (c) once per
  Newton step in G's form and two (c0, dc) once per CG iteration in
  DG's form;
- ``sms_adjoint``, the adjoint chain, reads two (the partitions'
  residuals and c0) once per Newton step and per CG iteration.

Their slice planes, the FOV plane and their outputs are not counted:
the compiled program keeps part of them in on-chip memory (described-chip
compile: memory space S(1) for the FOV, G's slice planes and half of the
outputs; every coil-stack input in HBM), so only the stacks' reads bound
the HBM traffic from below.  Counting every operand read a first traced
run at 105.1% of the HBM roofline.

With the coils split over ``chips`` each chip's kernels see J/chips
channels; the least time is the total spread evenly over the chips.
"""

from __future__ import annotations

from chipbench.work.nlinv import C64, fft_batch_work, fft_batches, \
    least_seconds


def stack_bytes(cfg: dict, chips: int = 1) -> float:
    """Bytes of one (M, J/chips, X, Y) complex64 coil stack on a chip."""
    g = 2 * int(cfg["n"])
    return int(cfg["slices"]) * (int(cfg["coils"]) // chips) * g * g * C64


def lincomb_bytes(cfg: dict, two_term: bool, chips: int = 1) -> float:
    """Least bytes one ``sms_lincomb`` call reads on one chip."""
    return (2 if two_term else 1) * stack_bytes(cfg, chips)


def adjoint_bytes(cfg: dict, chips: int = 1) -> float:
    """Least bytes one ``sms_adjoint`` call reads on one chip."""
    return 2 * stack_bytes(cfg, chips)


def mix_bytes(cfg: dict, newton_steps: int, calls_lincomb: int,
              calls_adjoint: int, chips: int = 1) -> float:
    """Least bytes of the mixing kernels' calls on one chip: of
    ``calls_lincomb`` forward calls, ``newton_steps`` are G's one-term
    form and the rest DG's two-term form."""
    return ((newton_steps * lincomb_bytes(cfg, False, chips))
            + (calls_lincomb - newton_steps) * lincomb_bytes(cfg, True,
                                                             chips)
            + calls_adjoint * adjoint_bytes(cfg, chips))


def frame_work(cfg: dict, cg_total: int) -> dict:
    """Least operations and bytes of one frame that runs ``cg_total`` CG
    iterations (all chips)."""
    M = int(cfg["slices"])
    newton = int(cfg["newton"])
    batches = fft_batches(newton, cg_total)
    ops, nbytes = fft_batch_work(M * int(cfg["coils"]), 2 * int(cfg["n"]))
    calls = newton + cg_total
    return {"fft_batches": batches, "flops": batches * ops,
            "bytes": batches * nbytes + mix_bytes(cfg, newton, calls,
                                                  calls)}


def frame_least_seconds(cfg: dict, cg_total: int, peak: dict,
                        chips: int) -> float:
    w = frame_work(cfg, cg_total)
    return least_seconds(w["flops"], w["bytes"], peak, chips)

"""Least work of one NLINV frame, counted from its shapes.

The count follows the algorithm, not the program: per Newton step the
coils c0 = IFFT(w chat) (1 batch of J 2-D FFTs), the forward model's FFT
(1) and the residual's adjoint, IFFT then FFT (2); per CG iteration the
derivative, IFFT of the coil update then FFT (2), and its adjoint (2);
per frame one more IFFT for the displayed image's coil combination.
With c0 computed once per Newton step this is the fewest FFT batches the
algorithm can run (paper Table 1 counts 3 + 3 per CG iteration because it
recomputes c0 inside DF and DF^H).  The CG stops on a relative residual
of 1e-6, which the first, strongly regularised Newton steps reach in
7-12 of their ``cg_iters``; so the CG iterations of a frame are counted
by the plain reference on the traffic itself, and the fewest that one of
its frames ran is used, so that the least time stays a lower bound.

A 2-D complex FFT of N = g*g points is counted as 5 N log2 N real
operations, and as one read and one write of its complex64 data: the
pointwise work between FFTs is taken to ride along in those passes, so
it adds no bytes of its own.  With the coils split over ``chips``, the
least time on the cell's chips is the total spread evenly over them.
"""

from __future__ import annotations

import math

C64 = 8   # bytes per complex64 value


def fft_batches(newton: int, cg_total: int) -> int:
    """Batches of J 2-D FFTs in one frame of ``newton`` steps that run
    ``cg_total`` CG iterations in all (see the module docstring)."""
    return 4 * newton + 4 * cg_total + 1


def fft_batch_work(coils: int, grid: int) -> tuple[float, float]:
    """(operations, bytes) of one batch of ``coils`` grid x grid FFTs."""
    n = grid * grid
    return 5.0 * coils * n * math.log2(n), 2.0 * coils * n * C64


def frame_work(cfg: dict, cg_total: int) -> dict:
    """Least operations and bytes of one frame of ``cfg`` that runs
    ``cg_total`` CG iterations (all chips)."""
    batches = fft_batches(int(cfg["newton"]), cg_total)
    ops, nbytes = fft_batch_work(int(cfg["coils"]), 2 * int(cfg["n"]))
    return {"fft_batches": batches, "flops": batches * ops,
            "bytes": batches * nbytes}


def least_seconds(flops: float, nbytes: float, peak: dict,
                  chips: int = 1) -> float:
    """Roofline time: the larger of operations over peak and bytes over
    bandwidth, with the work spread evenly over ``chips``."""
    return max(flops / peak["flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"]) / chips


def frame_least_seconds(cfg: dict, cg_total: int, peak: dict,
                        chips: int) -> float:
    w = frame_work(cfg, cg_total)
    return least_seconds(w["flops"], w["bytes"], peak, chips)

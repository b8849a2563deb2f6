"""The NLINV problem: single-slice real-time radial frames, each
reconstructed by the paper's IRGNM (arXiv:1301.1215 section 3).

A configuration names its problem with ``"problem": "<name>"``, and the
harness loads ``chipbench/problems/<name>.py`` from the benchmark root.
A problem module provides:

- ``make_traffic(cfg, mix, seed) -> dict``: from the seed alone; the
  harness reads only ``"movies"``, one entry per scanner;
- ``describe(traffic) -> str``: what one frame is, for the log;
- ``reference_movie(cfg, traffic, scanner, frames, device, lowp=False)
  -> (images, work_per_frame)``: the plain reference chain of one
  scanner (``lowp=True`` is the control, one precision step down) and
  the work count of each frame that ``frame_least_seconds`` takes;
- ``frame_least_seconds(cfg, work, peak, chips) -> float``: the least
  time of one frame's work on the cell's chips;
- ``service(cfg, mix, traffic, comm) -> (workload, opened, item)``: the
  ``repro.serve.Workload`` the scheduler runs, each scanner's keyword
  arguments to ``StreamScheduler.open``, and ``item(scanner, m)``, what
  ``submit`` is handed for movie frame ``m``;
- ``stream(cfg, mix, traffic, comm) -> run``: ``run(scanner, m, carry)
  -> (image, carry, frame_ms)``, one program call of the stream entry.

Here the traffic, the reference and the work count are
``chipbench/traffic.py``, ``chipbench/reference.py`` and
``chipbench/work/nlinv.py``.
"""

from __future__ import annotations

from chipbench import reference
from chipbench.traffic import make_traffic  # noqa: F401  (the interface)
from chipbench.work.nlinv import frame_least_seconds  # noqa: F401


def describe(traffic: dict) -> str:
    return f"{traffic['movies'][0]['y'].shape[1:]} k-space"


def reference_movie(cfg: dict, traffic: dict, scanner: int, frames: int,
                    device, lowp: bool = False):
    mv = traffic["movies"][scanner]
    return reference.movie(mv["y"], mv["masks"], traffic["fov"],
                           newton=int(cfg["newton"]),
                           cg_iters=int(cfg["cg_iters"]),
                           damping=float(cfg["assumed"]["damping"]),
                           frames=frames, lowp=lowp, device=device)


def _reconstructor(cfg: dict, comm):
    from repro.nlinv.recon import Reconstructor
    return Reconstructor(comm, newton=int(cfg["newton"]),
                         cg_iters=int(cfg["cg_iters"]),
                         channel_sum=cfg["channel_sum"])


def service(cfg: dict, mix: dict, traffic: dict, comm):
    """An ``NlinvStreamWorkload``: the batched solve over a persistent
    carry stack, each frame uploaded at submit."""
    from repro.serve import NlinvStreamWorkload
    workload = NlinvStreamWorkload(_reconstructor(cfg, comm),
                                   damping=float(cfg["assumed"]["damping"]))
    opened = [dict(grid=traffic["grid"], ncoils=traffic["coils"],
                   fov=traffic["fov"]) for _ in traffic["movies"]]

    def item(scanner: int, m: int):
        mv = traffic["movies"][scanner]
        return mv["y"][m], mv["masks"][m]

    return workload, opened, item


def stream(cfg: dict, mix: dict, traffic: dict, comm):
    """``FrameStream.run`` on one frame per call, the Newton carry handed
    from call to call."""
    from repro.nlinv.stream import FrameStream
    fs = FrameStream(_reconstructor(cfg, comm),
                     damping=float(cfg["assumed"]["damping"]))

    def run(scanner: int, m: int, carry):
        mv = traffic["movies"][scanner]
        imgs, report = fs.run(mv["y"][m:m + 1], mv["masks"][m:m + 1],
                              traffic["fov"], carry=carry)
        return imgs[0], fs.last_carry, report.frame_ms

    return run

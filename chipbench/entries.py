"""The user entries a window drives, one class per traffic ``entry``.

Each takes the configuration, the mix, the traffic and the
communicator of the program under test, and serves one frame per scanner
per ``round``: the closed loop hands a scanner its next frame only once
the previous image is on the host.  Latency is the benchmark's own clock
from the hand-over to the image on the host, as a display would have it.
"""

from __future__ import annotations

import time

import jax
import numpy as np


def _span(name: str):
    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


class Served:
    """One frame's outcome: latency (ms), the host image, or a failure."""

    __slots__ = ("scanner", "frame", "latency_ms", "image", "failure")

    def __init__(self, scanner, frame, latency_ms=None, image=None,
                 failure=None):
        self.scanner, self.frame = scanner, frame
        self.latency_ms, self.image, self.failure = latency_ms, image, failure


class ServiceEntry:
    """Scanners through ``StreamScheduler.open/submit/tick`` over an
    ``NlinvStreamWorkload``: one batched launch per tick serves one frame
    of every scanner."""

    def __init__(self, cfg, mix, traffic, comm):
        from repro.nlinv.recon import Reconstructor
        from repro.serve import (NlinvStreamWorkload, Rejected, ServeConfig,
                                 StreamScheduler)
        self._rejected = Rejected
        self.traffic = traffic
        rec = Reconstructor(comm, newton=int(cfg["newton"]),
                            cg_iters=int(cfg["cg_iters"]),
                            channel_sum=cfg["channel_sum"])
        k = int(mix["scanners"])
        self.sched = StreamScheduler(
            NlinvStreamWorkload(rec, damping=float(cfg["assumed"]["damping"])),
            ServeConfig(max_concurrency=k, buckets=(int(mix["bucket"]),)))
        self.sessions = [
            self.sched.open(client=f"scanner{i}", grid=traffic["grid"],
                            ncoils=traffic["coils"], fov=traffic["fov"])
            for i in range(k)]

    def round(self, f: int) -> list:
        out, handed = [], []
        for i, s in enumerate(self.sessions):
            mv = self.traffic["movies"][i]
            m = f % len(mv["y"])
            t0 = time.perf_counter()
            before = len(s.results)
            with _span("submit"):
                ok = self.sched.submit(s, (mv["y"][m], mv["masks"][m]))
            handed.append((i, s, t0, before))
            if not ok:
                out.append(Served(i, f, failure="shed"))
        with _span("step"):
            self.sched.tick()
        for i, s, t0, before in handed:
            if len(s.results) == before:
                continue                      # shed at submit
            r = s.results[-1]
            if isinstance(r, self._rejected):
                out.append(Served(i, f, failure=r.reason))
                continue
            with _span("fetch"):
                img = np.asarray(r)
            out.append(Served(i, f, (time.perf_counter() - t0) * 1e3, img))
        return out

    def step_ms(self) -> list:
        """The program's own timer: wall ms of each tick."""
        return list(self.sched.tick_ms)

    def degraded(self) -> int:
        """Steps the scheduler took on its deadline ladder, each one
        fewer Newton or CG steps or a narrower batch."""
        return len(self.sched.events)

    def close(self):
        self.sched = self.sessions = None


class StreamEntry:
    """One scanner through ``FrameStream.run``, one frame per call, the
    Newton carry handed from call to call with ``carry=``."""

    def __init__(self, cfg, mix, traffic, comm):
        from repro.nlinv.recon import Reconstructor
        from repro.nlinv.stream import FrameStream
        if int(mix["scanners"]) != 1:
            raise ValueError("the stream entry serves one scanner")
        self.traffic = traffic
        rec = Reconstructor(comm, newton=int(cfg["newton"]),
                            cg_iters=int(cfg["cg_iters"]),
                            channel_sum=cfg["channel_sum"])
        self.fs = FrameStream(rec, damping=float(cfg["assumed"]["damping"]))
        self.carry = None
        self._frame_ms = []

    def round(self, f: int) -> list:
        mv = self.traffic["movies"][0]
        m = f % len(mv["y"])
        t0 = time.perf_counter()
        with _span("step"):
            imgs, report = self.fs.run(mv["y"][m:m + 1], mv["masks"][m:m + 1],
                                       self.traffic["fov"], carry=self.carry)
        self.carry = self.fs.last_carry
        with _span("fetch"):
            img = np.asarray(imgs[0])
        self._frame_ms.extend(report.frame_ms)
        return [Served(0, f, (time.perf_counter() - t0) * 1e3, img)]

    def step_ms(self) -> list:
        """The program's own timer: ``LatencyReport.frame_ms``."""
        return list(self._frame_ms)

    def degraded(self) -> int:
        """``FrameStream`` has no deadline ladder."""
        return 0

    def close(self):
        self.fs = self.carry = None


ENTRIES = {"service": ServiceEntry, "stream": StreamEntry}

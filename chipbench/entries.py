"""The user entries a window drives, one class per traffic ``entry``.

Each takes the configuration, the mix, the traffic, the communicator of
the program under test and the cell's problem (``chipbench/problems/``),
which builds the program and says what one frame is; the entry serves
one frame per scanner per ``round``: the closed loop hands a scanner its
next frame only once the previous image is on the host.  Latency is the
benchmark's own clock from the hand-over to the image on the host, as a
display would have it.
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
    """Scanners through ``StreamScheduler.open/submit/tick`` over the
    problem's workload: one batched launch per tick serves one frame of
    every scanner."""

    def __init__(self, cfg, mix, traffic, comm, problem):
        from repro.serve import Rejected, ServeConfig, StreamScheduler
        self._rejected = Rejected
        self.frames = int(mix["movie_frames"])
        workload, opened, self._item = problem.service(cfg, mix, traffic,
                                                       comm)
        k = int(mix["scanners"])
        self.sched = StreamScheduler(
            workload,
            ServeConfig(max_concurrency=k, buckets=(int(mix["bucket"]),)))
        self.sessions = [self.sched.open(client=f"scanner{i}", **opened[i])
                         for i in range(k)]

    def round(self, f: int) -> list:
        out, handed = [], []
        m = f % self.frames
        for i, s in enumerate(self.sessions):
            t0 = time.perf_counter()
            before = len(s.results)
            with _span("submit"):
                ok = self.sched.submit(s, self._item(i, m))
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
        self.sched = self.sessions = self._item = None


class StreamEntry:
    """One scanner through the problem's stream call, one frame per
    call, the carry handed from call to call."""

    def __init__(self, cfg, mix, traffic, comm, problem):
        if int(mix["scanners"]) != 1:
            raise ValueError("the stream entry serves one scanner")
        self.frames = int(mix["movie_frames"])
        self._run = problem.stream(cfg, mix, traffic, comm)
        self.carry = None
        self._frame_ms = []

    def round(self, f: int) -> list:
        m = f % self.frames
        t0 = time.perf_counter()
        with _span("step"):
            image, self.carry, frame_ms = self._run(0, m, self.carry)
        with _span("fetch"):
            img = np.asarray(image)
        self._frame_ms.extend(frame_ms)
        return [Served(0, f, (time.perf_counter() - t0) * 1e3, img)]

    def step_ms(self) -> list:
        """The program's own timer, per call (``LatencyReport.frame_ms``
        for NLINV)."""
        return list(self._frame_ms)

    def degraded(self) -> int:
        """The stream entry has no deadline ladder."""
        return 0

    def close(self):
        self._run = self.carry = None


ENTRIES = {"service": ServiceEntry, "stream": StreamEntry}

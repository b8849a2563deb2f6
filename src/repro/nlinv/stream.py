"""Real-time streaming frame engine (the paper's raison d'être: §1's
latency-bounded "real-time applications", and the 2017 follow-up's
streaming NLINV service).

Temporal regularization makes frame *f+1* depend on the damped solution
of frame *f*, so frames cannot be reconstructed in parallel — but the
host→device transfer of the *next* acquisition can overlap the Newton
iterations of the current one.  ``FrameStream``:

  * double-buffers acquisition upload: while the solver of frame ``f``
    is in flight (JAX dispatch is asynchronous), frame ``f+1``'s coil
    data is already being scattered (NATURAL over the group) and its
    sampling mask broadcast — through the ``Communicator`` verbs
    (``container``/``bcast``), never raw device_put+specs;
  * donates the Newton carry (``x0``/``x_ref``) to the solver so XLA
    reuses the two largest buffers frame-to-frame
    (``Reconstructor.fn_donate_carry``);
  * records per-frame wall-clock latency and jitter — the real-time
    budget of the application — into a ``LatencyReport`` artifact.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.runtime import span
from ..lib.plan import default_cache
from ..task import Executor, Pipeline, TaskGraph
from .operators import sobolev_weight
from .recon import Reconstructor, pad_channels


def latency_stats(samples_ms) -> dict:
    """Steady-state latency statistics over per-call wall-clock samples
    (milliseconds).  Shared between the streaming LatencyReport and the
    ``repro.bench`` timing harness so every latency number in the repo
    is computed one way."""
    arr = np.asarray(list(samples_ms), dtype=np.float64)
    if arr.size == 0:
        arr = np.zeros(1)
    mean = float(arr.mean())
    if arr.size < 2:
        # a single-sample window has no spread: the percentiles ARE the
        # sample and the jitter is exactly zero — never interpolation
        # noise (a one-frame client in the serving report must not show
        # phantom jitter).
        one = round(float(arr[0]), 3)
        p50, p95, jitter = one, one, 0.0
    else:
        p50 = round(float(np.percentile(arr, 50)), 3)
        p95 = round(float(np.percentile(arr, 95)), 3)
        jitter = round(float(arr.std()), 3)
    return {
        "mean_ms": round(mean, 3),
        "p50_ms": p50,
        "p95_ms": p95,
        "jitter_ms": jitter,
        "fps": round(1e3 / max(mean, 1e-9), 2),
    }


def upload_frame(rec: "Reconstructor", y, mask):
    """Stage one acquisition onto the group: coil data NATURAL-scattered,
    sampling mask broadcast — the single upload step both the streaming
    loop and the serving scheduler issue (always through the verbs,
    never raw device_put+specs).  ``y`` must already be channel-padded
    to the group size."""
    with span("nlinv.upload"):
        return rec.put_frame(np.asarray(y)), rec.put_const(np.asarray(mask))


def damper(damping: float):
    """The jitted temporal-regularization reference ``u -> damping * u``
    (device scope ``nlinv.damp``)."""
    @jax.named_scope("nlinv.damp")
    def damp(u):
        return jax.tree.map(lambda a: damping * a, u)
    return jax.jit(damp)


class DoubleBuffer:
    """One-slot-ahead host→device staging.

    JAX dispatch is asynchronous, so an upload issued right after a
    solver launch lands while the solve is still in flight.  ``stage``
    issues the upload for the NEXT item; ``take`` hands over the staged
    device buffers (exactly once).  ``FrameStream`` primes it with frame
    0 and restages behind every launch; the serving scheduler keeps one
    per session and stages at enqueue time, so every client's next frame
    rides behind the current batched tick."""

    def __init__(self, upload):
        self._upload = upload
        self._slot = None

    @property
    def ready(self) -> bool:
        return self._slot is not None

    def stage(self, *args) -> None:
        if self._slot is not None:
            raise RuntimeError("DoubleBuffer.stage: slot already staged "
                               "(take() the in-flight item first)")
        self._slot = self._upload(*args)

    def take(self):
        if self._slot is None:
            raise RuntimeError("DoubleBuffer.take: nothing staged")
        slot, self._slot = self._slot, None
        return slot


def _frames(rec: "Reconstructor", y):
    """A movie's coil data channel-padded to the group: ``(y, frames,
    grid, padded coils)``; the coil axis follows the frame axis and the
    reconstructor's slice axis, if any."""
    y = np.asarray(y)
    ax = 1 + rec.coil_dim
    if y.ndim != 4 + rec.coil_dim:
        raise ValueError(f"y of shape {y.shape} is not a movie of "
                         f"{rec.slices}-slice frames")
    y = pad_channels(y, rec.comm.size, axis=ax)
    return y, y.shape[0], y.shape[-1], y.shape[ax]


@dataclasses.dataclass
class LatencyReport:
    """Per-frame wall-clock of one streaming run (milliseconds), plus
    the plan-cache evidence that the steady state builds nothing."""

    frame_ms: list[float]
    devices: int
    grid: int
    ncoils: int
    # plans built while each frame was processed (library-port cache
    # misses; frame 0 pays them all, steady-state frames must show 0)
    frame_plan_builds: list[int] = dataclasses.field(default_factory=list)
    plan_stats: dict = dataclasses.field(default_factory=dict)
    # frames the pipeline DROPPED (dispatch failure under
    # ``drop_failed``): frozen in the movie, excluded from the latency
    # statistics — a dropped frame has no latency, it has an error
    dropped: list = dataclasses.field(default_factory=list)

    def summary(self) -> dict:
        """First frame pays compilation; steady-state stats exclude it
        (and dropped frames, which never completed)."""
        gone = set(self.dropped)
        completed = [t for i, t in enumerate(self.frame_ms)
                     if i not in gone]
        if not completed:
            completed = [0.0]
        steady = completed[1:] if len(completed) > 1 else completed
        out = {
            "frames": len(self.frame_ms),
            "devices": self.devices,
            "grid": self.grid,
            "ncoils": self.ncoils,
            "first_frame_ms": round(completed[0], 3),
            **latency_stats(steady),
            "frame_ms": [round(t, 3) for t in self.frame_ms],
        }
        if self.dropped:
            out["dropped"] = list(self.dropped)
        if self.frame_plan_builds:
            out["plan_cache"] = dict(
                self.plan_stats,
                frame_builds=list(self.frame_plan_builds),
                steady_builds=int(sum(self.frame_plan_builds[1:])))
        return out

    def save(self, path) -> pathlib.Path:
        path = pathlib.Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.summary(), indent=2) + "\n")
        return path


class FrameStream:
    """Streaming movie reconstruction over a ``Reconstructor``."""

    def __init__(self, recon: Reconstructor, *, damping: float = 0.9,
                 donate_carry: bool = True):
        self.recon = recon
        self.damping = damping
        self.donate_carry = donate_carry
        self.last_carry = None      # {"u", "x_ref"} after run() (fenced)
        self._damp = damper(damping)

    def run(self, y, masks, fov, *, weight=None, carry=None,
            report_path=None) -> tuple[jax.Array, LatencyReport]:
        """Reconstruct a movie: y (F, J, X, Y), masks (F, X, Y); with the
        reconstructor's ``slices`` M > 1 (SMS-NLINV) y (F, M, J, X, Y),
        masks (F, M, X, Y) and images (F, M, X, Y).

        Returns (images (F, X, Y), LatencyReport).  Writes the report
        artifact to ``report_path`` when given.  ``carry`` resumes from
        a previous run's ``last_carry`` (checkpoint restore / elastic
        continuation) instead of a cold ``init_carry``; with
        ``donate_carry`` the passed-in buffers are donated to frame 0.
        """
        rec = self.recon
        with span("stream.prepare"):
            y, F, g, J = _frames(rec, y)
            if weight is None:
                weight = sobolev_weight(g)

            fov_d = rec.put_const(np.asarray(fov))
            w_d = rec.put_const(np.asarray(weight))
            if carry is None:
                u = rec.init_carry(J, g)
                # x_ref starts equal to u but must be a distinct buffer:
                # both are donated to the solver every frame.
                x_ref = jax.tree.map(lambda a: a + 0, u)
            else:
                u, x_ref = carry["u"], carry["x_ref"]
            fn = rec.fn_donate_carry if self.donate_carry else rec.fn

        cache = getattr(rec, "plan_cache", default_cache())
        run_start = cache.snapshot()
        images, frame_ms, frame_builds = [], [], []
        # prime the double buffer with frame 0
        buf = DoubleBuffer(lambda f: upload_frame(rec, y[f], masks[f]))
        buf.stage(0)
        for f in range(F):
            t0 = time.perf_counter()
            builds0 = cache.builds
            with span("stream.launch", frame=f):
                yd, md = buf.take()
                u, img = fn(yd, md, fov_d, w_d, u, x_ref)
                # the solver is now in flight; upload frame f+1 behind it
                if f + 1 < F:
                    buf.stage(f + 1)
                x_ref = self._damp(u)
            with span("stream.wait", frame=f):
                img.block_until_ready()
            frame_ms.append((time.perf_counter() - t0) * 1e3)
            # plans built during this frame: geometry setup (frame 0
            # traces the solver, building its fft/frame plans); the
            # steady state must be all hits — the report proves it.
            frame_builds.append(cache.builds - builds0)
            images.append(img)

        with span("stream.finish"):
            self.last_carry = jax.block_until_ready(
                {"u": u, "x_ref": x_ref})
            # report per-RUN counter deltas, not the process-global
            # cumulative stats — the artifact must describe this stream.
            run = cache.delta(run_start)
            report = LatencyReport(frame_ms, rec.comm.size, g, J,
                                   frame_plan_builds=frame_builds,
                                   plan_stats=run)
            if report_path is not None:
                report.save(report_path)
            return jnp.stack(images), report


def frame_graph(rec: "Reconstructor", take_upload, damp) -> TaskGraph:
    """One streamed frame of the NLINV program as a :class:`TaskGraph`.

    Four nodes, all placed on the reconstructor's group:

      ``upload``  (copy edge) host→device staging of the acquisition —
                  takes the double-buffered slot and restages the next
                  frame behind the in-flight work;
      ``solve``   the Newton/CG stage (``Reconstructor.fn_solve``);
      ``damp``    the temporal-regularization reference for frame f+1;
      ``crop``    the readout/channel-combination stage
                  (``Reconstructor.fn_image``).

    Cross-frame dependencies enter as feeds: ``u_prev``/``xref_prev``
    are the previous frame's (possibly still in-flight) ``u``/``xref``
    values, plus the replicated constants ``fov``/``weight``.  The
    :class:`repro.task.Pipeline` keeps several of these graphs in
    flight, so the upload of frame f+2, the solve of frame f+1 and the
    crop of frame f all sit on the device queue concurrently — the
    multi-stage schedule of arXiv:1701.08361 §3 instead of the rigid
    two-stage overlap."""
    g = TaskGraph()
    g.copy("upload", take_upload, outputs=("y", "mask"), group=rec.comm)
    g.add("solve", rec.fn_solve,
          inputs=("y", "mask", "fov", "weight", "u_prev", "xref_prev"),
          outputs=("u",), group=rec.comm)
    g.add("damp", damp, inputs=("u",), outputs=("xref",), group=rec.comm)
    g.add("crop", rec.fn_image, inputs=("mask", "fov", "weight", "u"),
          outputs=("img",), group=rec.comm)
    return g


class FramePipeline:
    """Task-graph pipelined streaming reconstruction (ISSUE 9).

    Same contract as :class:`FrameStream` — ``run(y, masks, fov) ->
    (images, LatencyReport)``, numerically the same movie — but the
    frame program runs as a :class:`repro.task.TaskGraph` through a
    rolling :class:`repro.task.Pipeline`: up to ``inflight`` frames'
    graphs stay dispatched-but-unfenced, so the host never stalls on
    frame f before issuing the upload/solve of frames f+1..f+inflight-1.
    Frames are still *sequentially dependent* (temporal regularization:
    frame f+1's solve consumes frame f's damped carry), so the device
    work cannot parallelize — what pipelining removes is the per-frame
    host fence and the dispatch/upload bubble behind it.

    ``frame_ms`` in the report is completion-to-completion time (the
    throughput view): with several frames in flight a per-frame
    dispatch-to-ready latency would double-count overlapped work.

    Fault tolerance: ``retry`` (a ``repro.ft.RestartPolicy``) arms the
    executor's transient-task retry; ``drop_failed=True`` turns a frame
    whose dispatch still fails into a DROP instead of a crash — the
    movie freezes on the last good image for that index, the carry
    keeps pointing at the last good frame (temporal regularization
    continues from it), and ``report.dropped`` lists the indices.  A
    real-time consumer prefers a repeated frame over a dead stream.
    """

    def __init__(self, recon: Reconstructor, *, damping: float = 0.9,
                 inflight: int = 2, retry=None, drop_failed: bool = False):
        self.recon = recon
        self.damping = damping
        self.inflight = inflight
        self.retry = retry
        self.drop_failed = drop_failed
        self.last_carry = None      # {"u", "x_ref"} after run() (fenced)
        self._damp = damper(damping)

    def run(self, y, masks, fov, *, weight=None, carry=None,
            report_path=None) -> tuple[jax.Array, LatencyReport]:
        rec = self.recon
        y, F, g, J = _frames(rec, y)
        if weight is None:
            weight = sobolev_weight(g)

        fov_d = rec.put_const(np.asarray(fov))
        w_d = rec.put_const(np.asarray(weight))
        if carry is None:
            u = rec.init_carry(J, g)
            x_ref = jax.tree.map(lambda a: a + 0, u)
        else:
            u, x_ref = carry["u"], carry["x_ref"]

        cache = getattr(rec, "plan_cache", default_cache())
        run_start = cache.snapshot()
        buf = DoubleBuffer(lambda f: upload_frame(rec, y[f], masks[f]))
        buf.stage(0)
        pipe = Pipeline(Executor(retry=self.retry),
                        inflight=self.inflight,
                        drop_failed=self.drop_failed)
        images: dict[int, jax.Array] = {}
        frame_ms = [0.0] * F
        frame_builds = [0] * F
        t0 = last = time.perf_counter()
        prev = {"u": u, "xref": x_ref}

        def retire(steps):
            nonlocal last
            for f_done, vals in steps:
                now = time.perf_counter()
                frame_ms[f_done] = (now - last) * 1e3
                last = now
                images[f_done] = vals["img"]

        for f in range(F):
            def take_upload(f=f):
                yd, md = buf.take()
                # restage: frame f+1's scatter/bcast issue behind the
                # solve dispatched right after this node
                if f + 1 < F:
                    buf.stage(f + 1)
                return yd, md

            builds0 = cache.builds
            vals, done = pipe.push(
                frame_graph(rec, take_upload, self._damp),
                feeds={"fov": fov_d, "weight": w_d,
                       "u_prev": prev["u"], "xref_prev": prev["xref"]},
                tag=f, outputs=("u", "xref", "img"))
            frame_builds[f] = cache.builds - builds0
            if vals is None:
                # frame f dropped (drop_failed): the fault may have hit
                # before or after the upload node ran, so resync the
                # double buffer to hold exactly frame f+1's acquisition;
                # prev still points at the last good carry — the next
                # solve regularizes against the last delivered frame
                if buf.ready:
                    buf.take()
                if f + 1 < F:
                    buf.stage(f + 1)
                continue
            prev = {"u": vals["u"], "xref": vals["xref"]}
            retire(done)
        retire(pipe.flush())
        self.last_carry = jax.block_until_ready(
            {"u": prev["u"], "x_ref": prev["xref"]})

        dropped = [f for f, _ in pipe.dropped]
        if len(dropped) == F:
            raise RuntimeError(
                f"every frame dropped ({F} dispatch failures) — "
                f"nothing to freeze on; first: {pipe.dropped[0][1]!r}")
        # freeze-frame: a dropped index repeats the last delivered
        # image (leading drops repeat zeros — no frame shipped yet)
        shaped = next(img for f, img in sorted(images.items()))
        prev_img = jnp.zeros_like(shaped)
        movie = []
        for f in range(F):
            prev_img = images.get(f, prev_img)
            movie.append(prev_img)

        report = LatencyReport(frame_ms, rec.comm.size, g, J,
                               frame_plan_builds=frame_builds,
                               plan_stats=cache.delta(run_start),
                               dropped=dropped)
        if report_path is not None:
            report.save(report_path)
        return jnp.stack(movie), report


def stream_movie(data, *, comm=None, newton=7, cg_iters=30, damping=0.9,
                 channel_sum="crop", fused=True, report_path=None,
                 pipelined=False, inflight=2):
    """Convenience wrapper: dataset dict -> (images, LatencyReport).
    ``comm`` is a Communicator (or DeviceGroup; None = 1 device);
    ``fused=False`` is the unfused escape hatch; ``pipelined=True``
    runs the task-graph :class:`FramePipeline` (``inflight`` frames on
    the device queue) instead of the two-stage :class:`FrameStream`."""
    rec = Reconstructor(comm, newton=newton, cg_iters=cg_iters,
                        channel_sum=channel_sum, fused=fused)
    if pipelined:
        eng = FramePipeline(rec, damping=damping, inflight=inflight)
    else:
        eng = FrameStream(rec, damping=damping)
    return eng.run(data["y"], data["masks"], data["fov"],
                   report_path=report_path)

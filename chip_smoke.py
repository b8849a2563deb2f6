"""Smoke run of the NLINV reconstruction service on a TPU at the paper's size.

    python chip_smoke.py              # the service on one chip
    python chip_smoke.py --chips 4    # coil-split service on 4 chips vs 1 chip

Drives the system's main path through the entry points a user calls:
two scanner clients stream 4 frames each through ``StreamScheduler``
(``open`` / ``submit`` / ``tick``) over an ``NlinvStreamWorkload``, at
the paper's problem size: matrix 384 on the doubled grid 768, 8 coil
channels, 11 radial spokes per frame, 7 Newton steps of 20 CG
iterations.  Each client's phantom acquisition comes from ``--seed``.
The service uses one batch width, so it runs one batched program.

Checks, each of which must pass:
  * every kernel spec on the solver's path resolved to its compiled
    Pallas kernel (no jnp fallback, no interpret mode);
  * every served image is finite;
  * each client's NLINV image beats the gridding baseline (NRMSE
    against the phantom inside the FOV);
  * one chip: client 0's served movie matches the unbatched frame
    program (``FrameStream`` over ``Reconstructor.fn``) on the same
    frames; ``--chips 4``: each client's 4-chip movie matches the same
    service on one chip.  Both within ``RTOL``, relative L2 over the
    movie.

Times, compile seconds and peak device memory are printed for reading,
not checked.  The script exits non-zero without a result line when JAX
finds no TPU or any check fails; the last line of its standard output
is the JSON result.  Everything runs in this one process.
"""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent
if not (REPO / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke: no repro package under {REPO / 'src'}; run this "
             f"script from a checkout of the repository")
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import Environment  # noqa: E402
from repro.core import comm as _comm  # noqa: E402
from repro.core.runtime import use_compile_cache  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.nlinv import phantom  # noqa: E402
from repro.nlinv.gridding import gridding_recon  # noqa: E402
from repro.nlinv.recon import Reconstructor  # noqa: E402
from repro.nlinv.stream import FrameStream  # noqa: E402
from repro.serve import (NlinvStreamWorkload, Rejected,  # noqa: E402
                         ServeConfig, StreamScheduler)

N = 384             # matrix size; the reconstruction grid is 2N = 768
COILS = 8           # compressed channels (paper §3)
SPOKES = 11
FRAMES = 4          # frames per client
CLIENTS = 2
NEWTON, CG_ITERS = 7, 20
DAMPING = 0.9
# Parity is judged as ||got - ref|| / ||ref|| over the whole movie.  The
# f32 solve itself sits about 2e-3 (relative L2) from an f64 solve of
# the same frame (CPU, grid 64, 7 Newton x 20 CG steps): truncated CG at
# small regularization amplifies rounding.  A change of summation order
# (the coil sum split across chips) may move the result by as much, so
# the bound is 5x that, far below what a wrong kernel gives.
RTOL = 1e-2

# the kernel specs the frame program traces through
MAIN_PATH_SPECS = ("cg_fused.cg_update", "cg_fused.xpby_dot",
                   "coil_mult.coil_lincomb", "coil_mult.plane_mult",
                   "coil_mult.coil_adjoint", "coil_mult.coil_forward",
                   "dft.dft2c")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_WRITE = "/jax/compilation_cache/cache_misses"   # recorded on write


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str):
    print(msg, flush=True)


def require_tpu(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU, JAX found {len(devs)} {devs[0].platform} "
             f"device(s)")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} TPU chips, JAX found "
             f"{len(devs)}")
    return devs


class CompileClock:
    """Backend compile seconds per program (a persistent-cache hit
    counts its retrieval time), and the persistent-cache hits and
    writes, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = collections.Counter()
        self.hits = self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == _BACKEND_COMPILE:
            self.seconds[kw.get("fun_name", "?")] += secs

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_WRITE:
            self.writes += 1

    def report(self, label: str):
        big = {k: round(v, 3) for k, v in self.seconds.most_common()
               if v >= 0.5}
        log(f"[{label}] compile s per program (>= 0.5 s): "
            f"{json.dumps(big)}")
        log(f"[{label}] compile s total {sum(self.seconds.values()):.3f} "
            f"over {len(self.seconds)} programs, persistent-cache hits "
            f"{self.hits}, writes {self.writes}")


def datasets(seed: int) -> list:
    return [phantom.make_dataset(n=N, ncoils=COILS, nspokes=SPOKES,
                                 frames=FRAMES, seed=seed + k)
            for k in range(CLIENTS)]


def serve(rec: Reconstructor, datas: list, label: str):
    """All clients through the scheduler, one frame each per tick.
    Returns ({client index: (F, X, Y) served movie}, tick ms)."""
    sched = StreamScheduler(
        NlinvStreamWorkload(rec, damping=DAMPING),
        ServeConfig(max_concurrency=len(datas), buckets=(len(datas),)))
    sessions = [sched.open(client=f"scanner{k}", grid=d["grid"],
                           ncoils=d["ncoils"], fov=d["fov"])
                for k, d in enumerate(datas)]
    for f in range(FRAMES):
        for s, d in zip(sessions, datas):
            if not sched.submit(s, (d["y"][f], d["masks"][f])):
                fail(f"[{label}] {s.client} frame {f} was shed")
        if sched.tick() != len(sessions):
            fail(f"[{label}] tick {f} did not serve every client")
    movies = {}
    for k, s in enumerate(sessions):
        bad = [r for r in s.results if isinstance(r, Rejected)]
        if bad:
            fail(f"[{label}] {s.client}: {bad[0].reason}")
        movies[k] = np.stack([np.asarray(r) for r in s.results])
    ticks = list(sched.tick_ms)
    log(f"[{label}] tick ms (first includes compile): "
        f"{[round(t, 3) for t in ticks]}; steady median "
        f"{float(np.median(ticks[1:])):.3f} ms per tick of "
        f"{len(sessions)} frames")
    return movies, ticks


def nrmse(img, truth, fov) -> float:
    m = np.asarray(fov) > 0
    a = np.abs(np.asarray(img))[m]
    b = np.abs(np.asarray(truth))[m]
    a /= max(a.max(), 1e-9)
    b /= max(b.max(), 1e-9)
    return float(np.sqrt(np.mean((a - b) ** 2)))


def check_finite(movies: dict, label: str):
    for k, mv in movies.items():
        if not np.isfinite(mv).all():
            fail(f"[{label}] client {k}: non-finite pixels in the served "
                 f"movie")
    log(f"[{label}] all {sum(len(m) for m in movies.values())} served "
        f"images finite")


def check_quality(movies: dict, datas: list, label: str):
    for k, mv in movies.items():
        d = datas[k]
        ours = [nrmse(mv[f], d["rho"][f], d["fov"]) for f in range(FRAMES)]
        grid = [nrmse(gridding_recon(d["y"][f], d["masks"][f], d["fov"]),
                      d["rho"][f], d["fov"]) for f in range(FRAMES)]
        log(f"[{label}] client {k}: NRMSE nlinv {np.mean(ours):.5f} "
            f"(per frame {np.round(ours, 5).tolist()}), gridding "
            f"{np.mean(grid):.5f}")
        if not np.mean(ours) < np.mean(grid):
            fail(f"[{label}] client {k}: NLINV does not beat gridding")


def check_parity(got, want, what: str):
    err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    peak = float(np.abs(got - want).max() / np.abs(want).max())
    log(f"parity {what}: relative L2 {err:.3e} (RTOL {RTOL:g}), "
        f"max|diff|/max|ref| {peak:.3e}")
    if not err <= RTOL:
        fail(f"parity {what}: relative L2 {err:.3e} > {RTOL:g}")


def check_kernels():
    tally = registry.tally()
    log(f"kernel impl tally: {json.dumps(tally, sort_keys=True)}")
    for spec in MAIN_PATH_SPECS:
        got = tally.get(spec, {})
        if set(got) != {"pallas"}:
            fail(f"{spec} resolved to {got or 'nothing'}, not only the "
                 f"compiled Pallas kernel")


def peak_memory(devs) -> list:
    out = []
    for d in devs:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def one_chip(seed: int, clock: CompileClock):
    comm = Environment().subgroup(1)
    rec = Reconstructor(comm, newton=NEWTON, cg_iters=CG_ITERS,
                        channel_sum="crop")
    t0 = time.perf_counter()
    datas = datasets(seed)
    log(f"data: {CLIENTS} clients x {FRAMES} frames, y {datas[0]['y'].shape} "
        f"complex64, made in {time.perf_counter() - t0:.1f} s")
    movies, _ = serve(rec, datas, "1 chip service")
    clock.report("1 chip service")
    check_finite(movies, "1 chip service")
    check_quality(movies, datas, "1 chip service")

    d0 = datas[0]
    ref, report = FrameStream(rec, damping=DAMPING).run(
        d0["y"], d0["masks"], d0["fov"])
    s = report.summary()
    log(f"[unbatched frame] frame ms {s['frame_ms']} (first includes "
        f"compile)")
    clock.report("after unbatched frame")
    check_parity(movies[0], np.asarray(ref),
                 "client 0 served (batched) vs unbatched frame, 1 chip")
    return comm


def four_chips(seed: int, clock: CompileClock):
    comm4 = Environment().subgroup(4)
    comm1 = Environment().subgroup(1)
    g = 2 * N
    payloads = {"mask (bool)": g * g, "fov (f32)": 4 * g * g,
                "weight (f32)": 4 * g * g, "carry rho (c64)": 8 * g * g}
    sched = {k: _comm.bcast_schedule(comm4.group, comm4.mesh_axes, v)
             for k, v in payloads.items()}
    log(f"4 chips: unified_memory={comm4.group.unified_memory}, mesh "
        f"{[d.id for d in comm4.mesh.devices.flat]}, bcast schedules "
        f"{json.dumps(sched)}; coil data: NATURAL host shard upload")
    datas = datasets(seed)
    movies = {}
    for label, comm in (("4 chip service", comm4), ("1 chip service", comm1)):
        rec = Reconstructor(comm, newton=NEWTON, cg_iters=CG_ITERS,
                            channel_sum="crop")
        movies[label], _ = serve(rec, datas, label)
        clock.report(label)
        check_finite(movies[label], label)
    check_quality(movies["4 chip service"], datas, "4 chip service")
    for k in range(CLIENTS):
        check_parity(movies["4 chip service"][k],
                     movies["1 chip service"][k],
                     f"client {k} served, 4 chips vs 1 chip")
    return comm4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the coil-split service on 4 chips and "
                         "compare it with 1 chip (nothing else)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    devs = require_tpu(args.chips)
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}; jax {jax.__version__}; compile cache {cache}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        comm = four_chips(args.seed, clock)
    else:
        comm = one_chip(args.seed, clock)
    check_kernels()
    log(f"peak bytes in use per chip: "
        f"{peak_memory(list(comm.mesh.devices.flat))}")
    log(f"wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The SMS-NLINV problem: M slices excited together in real-time radial
FLASH, each frame reconstructed by the IRGNM of Rosenzweig et al.,
SMS-NLINV, Magn Reson Med 79:2057-2066 (2018).  The interface is
``chipbench/problems/nlinv.py``'s; the traffic is made here, the
reference is ``chipbench/reference_sms.py`` and the work count
``chipbench/work/sms_nlinv.py``.

Traffic, from the seed (the single-slice model of ``chipbench/traffic.py``
with a slice axis):

- slice m of M is a Shepp-Logan phantom cut at its own height z_m (the
  ellipses shrink and the contrast changes with |z_m|) that moves with a
  phase of its own;
- coil j's sensitivity in slice m is the birdcage map with a gain and a
  phase that vary along z, as a ring array's z-profile gives, normalised
  to unit root-sum-of-squares per slice;
- partition q of frame f is sampled by 11 golden-angle spokes, turned by
  q pi / (11 M) so that the M partitions interleave;
- y_qj = P_q FFT( Sum_m Xi_qm rho_m c_mj ) + complex Gaussian noise on
  the sampled points, Xi the DFT phase cycle.

Every seed gives the same frames; the seed draws the noise and where each
scanner's cycle starts, as in the single-slice traffic.
"""

from __future__ import annotations

import inspect

import numpy as np

from chipbench import reference_sms
from chipbench.traffic import (_ELLIPSES, GOLDEN, birdcage_coils, fov_mask,
                               radial_mask)
from chipbench.work.sms_nlinv import frame_least_seconds  # noqa: F401


def slice_heights(slices: int) -> np.ndarray:
    """z_m of the M slices, evenly spread over [-0.5, 0.5]."""
    return np.linspace(-0.5, 0.5, slices) if slices > 1 else np.zeros(1)


def slice_phantom(n: int, motion: float, z: float) -> np.ndarray:
    """(n, n) Shepp-Logan cut at height ``z``: the ellipses scaled by
    sqrt(1 - z^2), the inner ones' contrast by (1 - |z|), moving with a
    phase that follows z."""
    y, x = np.mgrid[-1:1:n * 1j, -1:1:n * 1j]
    scale = np.sqrt(1.0 - z * z)
    img = np.zeros((n, n), np.float32)
    for i, (a, ea, eb, x0, y0, phi) in enumerate(_ELLIPSES):
        dx = motion * 0.05 * np.sin(2 * np.pi * (motion + z) + i)
        th = np.deg2rad(phi)
        xs, ys = (x - scale * x0 - dx) / scale, (y - scale * y0) / scale
        xr = xs * np.cos(th) + ys * np.sin(th)
        yr = -xs * np.sin(th) + ys * np.cos(th)
        img[(xr / ea) ** 2 + (yr / eb) ** 2 <= 1.0] += \
            a if i < 2 else a * (1.0 - abs(z))
    return img


def slice_coils(n: int, ncoils: int, z: float) -> np.ndarray:
    """(J, n, n) birdcage maps of one slice: coil j's gain falls off with
    the distance of slice z from the coil's own height, and its phase
    turns with z; unit root-sum-of-squares."""
    c = birdcage_coils(n, ncoils)
    zc = 0.5 * np.cos(2 * np.pi * np.arange(ncoils) / ncoils)
    gain = np.exp(-((z - zc) ** 2) / 0.5)
    c = c * (gain * np.exp(1j * np.pi * z * (1 + np.arange(ncoils))
                           / ncoils))[:, None, None]
    rss = np.sqrt((np.abs(c) ** 2).sum(0, keepdims=True))
    return (c / np.maximum(rss, 1e-6)).astype(np.complex64)


def _kspace(n: int, J: int, slices: int, motion: float,
            coils: np.ndarray) -> np.ndarray:
    """(Q, J, 2n, 2n) fully sampled, slice-encoded k-space of one motion
    phase: Sum_m Xi_qm rho_m c_mj, Fourier transformed."""
    grid, q = 2 * n, n // 2
    img = np.zeros((slices, J, grid, grid), np.complex64)
    for m, z in enumerate(slice_heights(slices)):
        rho = slice_phantom(n, motion, z)
        img[m, :, q:q + n, q:q + n] = rho[None] * coils[m]
    xi = reference_sms.phase_cycle(slices)
    mixed = np.einsum("qm,mjxy->qjxy", xi, img).astype(np.complex64)
    ax = (-2, -1)
    return np.fft.fftshift(np.fft.fft2(np.fft.ifftshift(mixed, axes=ax),
                                       axes=ax, norm="ortho"),
                           axes=ax).astype(np.complex64)


def _require_slices() -> None:
    """Refuse, before the traffic is made, a program whose reconstructor
    has no slice axis."""
    from repro.nlinv.recon import Reconstructor

    from chipbench.bench import BenchError
    if "slices" not in inspect.signature(Reconstructor).parameters:
        raise BenchError("the program has no SMS-NLINV: Reconstructor "
                         "takes no slices")


def make_traffic(cfg: dict, mix: dict, seed: int) -> dict:
    """All scanners' movies: ``y`` (F, M, J, 2n, 2n) complex64 and
    ``masks`` (F, M, 2n, 2n) bool per scanner, from ``seed`` alone."""
    _require_slices()
    n, J, M = int(cfg["n"]), int(cfg["coils"]), int(cfg["slices"])
    spokes = int(cfg["assumed"]["spokes"])
    frames = int(mix["movie_frames"])
    noise = float(mix["noise"])
    coils = np.stack([slice_coils(n, J, z) for z in slice_heights(M)])
    # the fully sampled k-space of each motion phase is the same for every
    # scanner; only the spokes, the noise and the cycle start differ
    full = [_kspace(n, J, M, f / frames, coils) for f in range(frames)]
    rng = np.random.default_rng(seed)
    movies = []
    for k in range(int(mix["scanners"])):
        shift = int(rng.integers(0, frames))
        ys, masks = [], []
        for f in ((shift + i) % frames for i in range(frames)):
            first = (k * frames + f) * GOLDEN
            mask = np.stack([radial_mask(2 * n, spokes,
                                         first + q * np.pi / (spokes * M))
                             for q in range(M)])
            ksp = full[f] * mask[:, None]
            idx = np.nonzero(np.broadcast_to(mask[:, None], ksp.shape))
            ksp[idx] += noise * (rng.standard_normal(idx[0].size)
                                 + 1j * rng.standard_normal(idx[0].size))
            ys.append(ksp.astype(np.complex64))
            masks.append(mask)
        movies.append({"y": np.stack(ys), "masks": np.stack(masks)})
    return {"movies": movies, "fov": fov_mask(2 * n), "grid": 2 * n,
            "coils": J, "slices": M}


def describe(traffic: dict) -> str:
    return f"{traffic['movies'][0]['y'].shape[1:]} SMS k-space"


def reference_movie(cfg: dict, traffic: dict, scanner: int, frames: int,
                    device, lowp: bool = False):
    mv = traffic["movies"][scanner]
    return reference_sms.movie(mv["y"], mv["masks"], traffic["fov"],
                               newton=int(cfg["newton"]),
                               cg_iters=int(cfg["cg_iters"]),
                               damping=float(cfg["assumed"]["damping"]),
                               frames=frames, lowp=lowp, device=device)


def _reconstructor(cfg: dict, comm):
    from repro.nlinv.recon import Reconstructor
    return Reconstructor(comm, newton=int(cfg["newton"]),
                         cg_iters=int(cfg["cg_iters"]),
                         channel_sum=cfg["channel_sum"],
                         slices=int(cfg["slices"]))


def service(cfg: dict, mix: dict, traffic: dict, comm):
    """An ``NlinvStreamWorkload`` over an M-slice reconstructor: the
    batched solve over a persistent carry stack, every partition's data
    and mask uploaded at submit."""
    from repro.serve import NlinvStreamWorkload
    workload = NlinvStreamWorkload(_reconstructor(cfg, comm),
                                   damping=float(cfg["assumed"]["damping"]))
    opened = [dict(grid=traffic["grid"], ncoils=traffic["coils"],
                   slices=traffic["slices"], fov=traffic["fov"])
              for _ in traffic["movies"]]

    def item(scanner: int, m: int):
        mv = traffic["movies"][scanner]
        return mv["y"][m], mv["masks"][m]

    return workload, opened, item


def stream(cfg: dict, mix: dict, traffic: dict, comm):
    """``FrameStream.run`` on one SMS frame per call, the carry handed
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


def slice_gaps(images: dict, refs: dict) -> list:
    """Worst relative L2 gap of each slice over the compared frames:
    ``images[scanner][frame]`` and ``refs[scanner][frame]`` are (M, X, Y)
    (a missing image counts as an infinite gap)."""
    worst = None
    for i, frames in refs.items():
        for f, want in frames.items():
            got = images.get(i, {}).get(f)
            gaps = [float("inf") if got is None else
                    float(np.linalg.norm(np.asarray(got[m], np.complex128)
                                         - want[m])
                          / np.linalg.norm(np.asarray(want[m],
                                                      np.complex128)))
                    for m in range(len(want))]
            worst = gaps if worst is None else list(map(max, worst, gaps))
    return worst or []

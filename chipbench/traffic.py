"""Scanner traffic for the NLINV cells, made on the host from the seed.

A copy of the repository's phantom acquisition model (Shepp-Logan
phantom, birdcage coils, golden-angle radial masks, k-space with complex
Gaussian noise on the sampled points), kept here so that the yardstick
does not move when the program's own generator does.  Every seed gives
every scanner the same frames (the same sizes, angles and motion); the
seed draws the noise and the order in which each scanner's cycle starts,
so that it moves the work as little as the data allows (the CG stops on
a residual test, so its iteration count follows the data).

A traffic mix is a JSON file ``chipbench/traffic/<mix>.json``::

    {"entry": "service" | "stream",   # which user entry the window drives
     "scanners": 2,                   # concurrent exams (closed loop each)
     "bucket": 2,                     # service batch width (one program)
     "movie_frames": 4,               # distinct frames, cycled by the window
     "check_frames": 3,               # window frames per scanner compared
     "noise": 1e-4}                   # k-space noise std on sampled points
"""

from __future__ import annotations

import numpy as np

# (intensity, a, b, x0, y0, phi): the standard Shepp-Logan ellipses
_ELLIPSES = (
    (1.0, 0.69, 0.92, 0.0, 0.0, 0.0),
    (-0.8, 0.6624, 0.874, 0.0, -0.0184, 0.0),
    (-0.2, 0.11, 0.31, 0.22, 0.0, -18.0),
    (-0.2, 0.16, 0.41, -0.22, 0.0, 18.0),
    (0.1, 0.21, 0.25, 0.0, 0.35, 0.0),
    (0.1, 0.046, 0.046, 0.0, 0.1, 0.0),
    (0.1, 0.046, 0.046, 0.0, -0.1, 0.0),
    (0.1, 0.046, 0.023, -0.08, -0.605, 0.0),
    (0.1, 0.023, 0.023, 0.0, -0.606, 0.0),
    (0.1, 0.023, 0.046, 0.06, -0.605, 0.0),
)
GOLDEN = np.pi * (3 - np.sqrt(5.0))


def shepp_logan(n: int, motion: float) -> np.ndarray:
    """(n, n) phantom; ``motion`` shifts the ellipses (a beating heart)."""
    y, x = np.mgrid[-1:1:n * 1j, -1:1:n * 1j]
    img = np.zeros((n, n), np.float32)
    for i, (a, ea, eb, x0, y0, phi) in enumerate(_ELLIPSES):
        dx = motion * 0.05 * np.sin(2 * np.pi * motion + i)
        th = np.deg2rad(phi)
        xr = (x - x0 - dx) * np.cos(th) + (y - y0) * np.sin(th)
        yr = -(x - x0 - dx) * np.sin(th) + (y - y0) * np.cos(th)
        img[(xr / ea) ** 2 + (yr / eb) ** 2 <= 1.0] += a
    return img


def birdcage_coils(n: int, ncoils: int) -> np.ndarray:
    """(J, n, n) smooth sensitivities on a ring, RSS-normalised."""
    y, x = np.mgrid[-1:1:n * 1j, -1:1:n * 1j]
    coils = []
    for j in range(ncoils):
        th = 2 * np.pi * j / ncoils
        cx, cy = 1.3 * np.cos(th), 1.3 * np.sin(th)
        mag = np.exp(-((x - cx) ** 2 + (y - cy) ** 2) / 1.8)
        pha = np.exp(1j * (th + 0.5 * (x * np.cos(th) + y * np.sin(th))))
        coils.append(mag * pha)
    c = np.stack(coils)
    rss = np.sqrt((np.abs(c) ** 2).sum(0, keepdims=True))
    return (c / np.maximum(rss, 1e-6)).astype(np.complex64)


def radial_mask(grid: int, nspokes: int, angle0: float) -> np.ndarray:
    """(grid, grid) bool mask of ``nspokes`` radial lines from ``angle0``."""
    mask = np.zeros((grid, grid), bool)
    c = grid // 2
    rr = np.arange(-c, c, 0.5)
    for s in range(nspokes):
        th = s * np.pi / nspokes + angle0
        xs = np.clip(np.round(c + rr * np.cos(th)).astype(int), 0, grid - 1)
        ys = np.clip(np.round(c + rr * np.sin(th)).astype(int), 0, grid - 1)
        mask[ys, xs] = True
    return mask


def fov_mask(grid: int) -> np.ndarray:
    """M_Omega: the centred half of the doubled grid."""
    m = np.zeros((grid, grid), np.float32)
    q = grid // 4
    m[q:3 * q, q:3 * q] = 1.0
    return m


def scanner_movie(rng: np.random.Generator, *, n: int, coils: np.ndarray,
                  spokes: int, frames: int, noise: float,
                  first: int) -> dict:
    """One scanner's cycled movie: ``y`` (F, J, 2n, 2n) complex64 sampled
    k-space and ``masks`` (F, 2n, 2n) bool.  The frames are golden-angle
    acquisitions ``first .. first + F - 1`` of one motion cycle, the same
    for every seed; the seed draws the noise and where in the cycle the
    movie starts."""
    grid, q = 2 * n, n // 2
    shift = int(rng.integers(0, frames))
    ys, masks = [], []
    for f in ((shift + i) % frames for i in range(frames)):
        rho = np.zeros((grid, grid), np.complex64)
        rho[q:q + n, q:q + n] = shepp_logan(n, motion=f / frames)
        mask = radial_mask(grid, spokes, (first + f) * GOLDEN)
        ksp = np.fft.fftshift(
            np.fft.fft2(np.fft.ifftshift(rho[None] * coils, axes=(-2, -1)),
                        axes=(-2, -1), norm="ortho"), axes=(-2, -1))
        ksp *= mask[None]
        idx = np.nonzero(np.broadcast_to(mask, ksp.shape))
        ksp[idx] += noise * (rng.standard_normal(idx[0].size)
                             + 1j * rng.standard_normal(idx[0].size))
        ys.append(ksp.astype(np.complex64))
        masks.append(mask)
    return {"y": np.stack(ys), "masks": np.stack(masks)}


def make_traffic(cfg: dict, mix: dict, seed: int) -> dict:
    """All scanners' movies for one run, from ``seed`` alone."""
    n, J = int(cfg["n"]), int(cfg["coils"])
    grid, q = 2 * n, n // 2
    coils = np.zeros((J, grid, grid), np.complex64)
    coils[:, q:q + n, q:q + n] = birdcage_coils(n, J)
    rng = np.random.default_rng(seed)
    frames = int(mix["movie_frames"])
    movies = [scanner_movie(rng, n=n, coils=coils,
                            spokes=int(cfg["assumed"]["spokes"]),
                            frames=frames, noise=float(mix["noise"]),
                            first=k * frames)
              for k in range(int(mix["scanners"]))]
    return {"movies": movies, "fov": fov_mask(grid), "grid": grid,
            "coils": J}

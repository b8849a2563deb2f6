"""Plain SMS-NLINV reference: the IRGNM of Rosenzweig et al., "Simultaneous
multi-slice MRI using Cartesian and radial FLASH and regularized nonlinear
inversion: SMS-NLINV", Magn Reson Med 79:2057-2066 (2018), written from
the equations in straightforward ``jax.numpy`` (complex64, no kernels, no
batching).

It imports nothing of the program under test and takes nothing it made.
Per frame, M slices m with images rho_m and coils c_mj = IFFT(w * chat_mj)
are measured in M partitions q, each with its own sampling mask P_q and
the RF phase cycle Xi_qm = exp(2 pi i q m / M):

    G(u)_qj = P_q FFT( fov * Sum_m Xi_qm rho_m c_mj )

and the IRGNM of the single-slice reference (``chipbench/reference.py``)
acts on the stacked unknowns u = (rho (M, X, Y), chat (M, J, X, Y)):

    (DG^H DG + a_n I) dx = DG^H (y - G(x_n)) - a_n (x_n - x_ref)
    a_n = a_0 q^n,  a_0 = 1,  q = 1/3

with ``cg_iters`` CG steps from zero (stopping early only at a relative
residual of 1e-6), ``newton`` times per frame; frame f starts from frame
f-1's solution and regularises towards 0.9 of it (``damping``).  The
image of slice m is rho_m * sqrt(Sum_j |c_mj|^2).

Departures from Rosenzweig et al.: the data are Cartesian-gridded radial
spokes (a mask per partition on the doubled grid, as the single-slice
benchmark does), not gridded through the point-spread function; the
Sobolev weight, the regularisation schedule and the damping are the
single-slice NLINV's (arXiv:1301.1215) and not re-tuned for SMS; Xi
carries no normalisation (its unitary form would divide by sqrt M), here
and in the program alike; no post-filtering of the images.

``lowp=True`` is the control: the same algebra with every array rounded
to bfloat16 (real and imaginary parts) after each operation, the
precision step below the configuration's complex64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import _bf16, _fft2c, _ifft2c, sobolev_weight


def phase_cycle(slices: int) -> np.ndarray:
    """Xi_qm = exp(2 pi i q m / M), (partition, slice)."""
    q = np.arange(slices)
    return np.exp(2j * np.pi * np.outer(q, q) / slices).astype(np.complex64)


@functools.lru_cache(maxsize=None)
def frame_fn(newton: int, cg_iters: int, lowp: bool = False):
    """Jitted ``(y, masks, fov, w, xi, rho, chat, rho_ref, chat_ref) ->
    (rho, chat, image, CG iterations run)`` for one SMS frame."""
    rnd = _bf16 if lowp else (lambda x: x)

    def coils(chat, w):
        return rnd(_ifft2c(rnd(chat * w)))

    def frame(y, masks, fov, w, xi, rho, chat, rho_ref, chat_ref):
        pm = masks[:, None]                     # P_q over the channels
        y = rnd(y * pm)

        def mix(img):                           # Sum_m Xi_qm img_m
            return rnd(jnp.einsum("qm,mjxy->qjxy", xi, img))

        def unmix(k):                           # Sum_q conj(Xi_qm) k_q
            return rnd(jnp.einsum("qm,qjxy->mjxy", jnp.conj(xi), k))

        def newton_step(_, state):
            rho, chat, a, its = state
            c0 = coils(chat, w)

            def dg(drho, dchat):
                img = rnd(drho[:, None] * c0 + rho[:, None]
                          * coils(dchat, w))
                return rnd(pm * _fft2c(rnd(fov * mix(img))))

            def dgh(r):
                z = rnd(fov * unmix(rnd(_ifft2c(rnd(pm * r)))))
                drho = rnd(jnp.sum(jnp.conj(c0) * z, axis=1))
                dchat = rnd(w * _fft2c(rnd(jnp.conj(rho)[:, None] * z)))
                return drho, dchat

            def normal(p):
                gr, gc = dgh(dg(*p))
                return rnd(gr + a * p[0]), rnd(gc + a * p[1])

            def dot(u, v):
                return (jnp.real(jnp.vdot(u[0], v[0]))
                        + jnp.real(jnp.vdot(u[1], v[1])))

            fwd = rnd(pm * _fft2c(rnd(fov * mix(rnd(rho[:, None] * c0)))))
            gr, gc = dgh(rnd(y - fwd))
            rhs = (rnd(gr + a * (rho_ref - rho)),
                   rnd(gc + a * (chat_ref - chat)))
            rs0 = dot(rhs, rhs)
            thresh = 1e-12 * rs0

            def cond(s):
                return jnp.logical_and(s[0] < cg_iters, s[4] > thresh)

            def body(s):
                i, x, r, p, rs = s
                ap = normal(p)
                step = rs / jnp.maximum(dot(p, ap), 1e-30)
                x = tuple(rnd(xi_ + step * pi) for xi_, pi in zip(x, p))
                r = tuple(rnd(ri - step * qi) for ri, qi in zip(r, ap))
                rs_new = dot(r, r)
                beta = rs_new / jnp.maximum(rs, 1e-30)
                p = tuple(rnd(ri + beta * pi) for ri, pi in zip(r, p))
                return i + 1, x, r, p, rs_new

            zero = (jnp.zeros_like(rho), jnp.zeros_like(chat))
            i, dx, _, _, _ = jax.lax.while_loop(
                cond, body, (0, zero, rhs, rhs, rs0))
            return (rnd(rho + dx[0]), rnd(chat + dx[1]),
                    a * np.float32(1 / 3), its + i)

        rho, chat, _, its = jax.lax.fori_loop(
            0, newton, newton_step, (rho, chat, jnp.float32(1.0), 0))
        c = coils(chat, w)
        img = rho * jnp.sqrt(jnp.sum(jnp.abs(c) ** 2, axis=1))
        return rho, chat, img, its

    return jax.jit(frame)


def movie(y, masks, fov, *, newton: int, cg_iters: int, damping: float,
          frames: int, lowp: bool = False, device=None):
    """The first ``frames`` images (M, X, Y) of one scanner's chain, as
    host arrays, and the CG iterations each frame ran.  ``y`` (F, M, J,
    X, Y) and ``masks`` (F, M, X, Y) are cycled like the window cycles
    them."""
    fn = frame_fn(newton, cg_iters, lowp)
    F, M, J, g, _ = y.shape
    put = functools.partial(jax.device_put, device=device)
    with jax.default_matmul_precision("highest"):
        w = put(sobolev_weight(g))
        fov_d = put(np.asarray(fov, np.float32))
        xi = put(phase_cycle(M))
        rho = put(np.ones((M, g, g), np.complex64))
        chat = put(np.zeros((M, J, g, g), np.complex64))
        rho_ref, chat_ref = rho, chat
        out, iters = [], []
        for f in range(frames):
            rho, chat, img, its = fn(put(y[f % F]),
                                     put(masks[f % F].astype(np.float32)),
                                     fov_d, w, xi, rho, chat, rho_ref,
                                     chat_ref)
            rho_ref, chat_ref = damping * rho, damping * chat
            out.append(np.asarray(img))
            iters.append(int(its))
    return out, iters

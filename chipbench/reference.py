"""Plain NLINV reference: the paper's IRGNM (arXiv:1301.1215 eq. 2-3),
written from the equations in straightforward ``jax.numpy``.

It imports nothing of the program under test and takes nothing it made:
the weights, masks and carries are rebuilt here from the configuration
and the traffic.  Per frame, with unknowns u = (rho, chat), coils
c_j = IFFT(w * chat_j), the forward model G(u)_j = P FFT(M rho c_j) and
the previous frame's damped solution as x_ref:

    (DG^H DG + a_n I) dx = DG^H (y - G(x_n)) - a_n (x_n - x_ref)
    a_n = a_0 q^n,  a_0 = 1,  q = 1/3

solved by ``cg_iters`` conjugate-gradient steps from zero (stopping early
only at a relative residual of 1e-6), ``newton`` times per frame.  The
image is rho * sqrt(sum_j |c_j|^2).  Frames of one scanner form a chain:
frame f starts from frame f-1's solution and regularises towards 0.9 of
it (``damping``).

``lowp=True`` is the control: the same algebra with every array rounded
to bfloat16 (real and imaginary parts) after each operation, the
precision step below the configuration's complex64.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sobolev_weight(grid: int, s: float = 32.0, l: int = 4) -> np.ndarray:
    """w(k) = (1 + s |k|^2)^(-l/2), |k| normalised to [-1, 1] (Uecker 2008)."""
    k = np.fft.fftshift(np.fft.fftfreq(grid))
    ky, kx = np.meshgrid(k, k, indexing="ij")
    return ((1.0 + s * 4.0 * (kx ** 2 + ky ** 2)) ** (-l / 2.0)).astype(
        np.float32)


def _fft2c(x):
    ax = (-2, -1)
    return jnp.fft.fftshift(jnp.fft.fft2(jnp.fft.ifftshift(x, axes=ax),
                                         norm="ortho"), axes=ax)


def _ifft2c(x):
    ax = (-2, -1)
    return jnp.fft.fftshift(jnp.fft.ifft2(jnp.fft.ifftshift(x, axes=ax),
                                          norm="ortho"), axes=ax)


def _bf16(x):
    if jnp.iscomplexobj(x):
        return jax.lax.complex(_bf16(jnp.real(x)), _bf16(jnp.imag(x)))
    return x.astype(jnp.bfloat16).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def frame_fn(newton: int, cg_iters: int, lowp: bool = False):
    """Jitted ``(y, mask, fov, w, rho, chat, rho_ref, chat_ref) ->
    (rho, chat, image, CG iterations run)`` for one frame."""
    rnd = _bf16 if lowp else (lambda x: x)

    def coils(chat, w):
        return rnd(_ifft2c(rnd(chat * w)))

    def frame(y, mask, fov, w, rho, chat, rho_ref, chat_ref):
        y = rnd(y * mask)

        def newton_step(_, state):
            rho, chat, a, its = state
            c0 = coils(chat, w)

            def dg(drho, dchat):
                img = rnd(fov * rnd(drho[None] * c0 + rho[None]
                                    * coils(dchat, w)))
                return rnd(mask * _fft2c(img))

            def dgh(r):
                z = rnd(fov * _ifft2c(rnd(mask * r)))
                drho = rnd(jnp.sum(jnp.conj(c0) * z, axis=0))
                dchat = rnd(w * _fft2c(rnd(jnp.conj(rho)[None] * z)))
                return drho, dchat

            def normal(p):
                gr, gc = dgh(dg(*p))
                return rnd(gr + a * p[0]), rnd(gc + a * p[1])

            def dot(u, v):
                return (jnp.real(jnp.vdot(u[0], v[0]))
                        + jnp.real(jnp.vdot(u[1], v[1])))

            res = rnd(y - rnd(mask * _fft2c(rnd(fov * rnd(rho[None] * c0)))))
            gr, gc = dgh(res)
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
                x = tuple(rnd(xi + step * pi) for xi, pi in zip(x, p))
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
        return (rho, chat, rho * jnp.sqrt(jnp.sum(jnp.abs(c) ** 2, axis=0)),
                its)

    return jax.jit(frame)


def movie(y, masks, fov, *, newton: int, cg_iters: int, damping: float,
          frames: int, lowp: bool = False, device=None):
    """The first ``frames`` images of one scanner's chain, as host arrays,
    and the CG iterations each frame ran.  ``y`` (M, J, X, Y) and
    ``masks`` (M, X, Y) are cycled like the window cycles them."""
    fn = frame_fn(newton, cg_iters, lowp)
    M, J, g, _ = y.shape
    put = functools.partial(jax.device_put, device=device)
    with jax.default_matmul_precision("highest"):
        w = put(sobolev_weight(g))
        fov_d = put(np.asarray(fov, np.float32))
        rho = put(np.ones((g, g), np.complex64))
        chat = put(np.zeros((J, g, g), np.complex64))
        rho_ref, chat_ref = rho, chat
        out, iters = [], []
        for f in range(frames):
            rho, chat, img, its = fn(put(y[f % M]),
                                put(masks[f % M].astype(np.float32)),
                                fov_d, w, rho, chat, rho_ref, chat_ref)
            rho_ref, chat_ref = damping * rho, damping * chat
            out.append(np.asarray(img))
            iters.append(int(its))
    return out, iters


def rel_l2(got, want) -> float:
    got = np.asarray(got, np.complex128)
    want = np.asarray(want, np.complex128)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))

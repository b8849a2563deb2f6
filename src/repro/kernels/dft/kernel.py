"""The centred, ortho-normalised 2-D DFT as a Pallas TPU kernel.

Each axis of an (N, N) plane, N = r * 128, is factored as
n = 128 n1 + n2 and k = k1 + r k2:

    X[k1 + r k2] = Sum_n2 W128^(n2 k2) WN^(n2 k1) Sum_n1 Wr^(n1 k1) x[128 n1 + n2]

The r-point sums run on the VPU over the r lane blocks of 128.  The
128-point sums and the twiddles WN^(n2 k1) are one matrix per k1,
G_k1 = [[A, -B], [B, A]] for A + iB = W128^(n2 k2) WN^(n2 k1), applied
to the [re | im] rows on the MXU at ``precision=HIGHEST`` as two
(256, 128) matmuls, one per part, so each output is two 128-term sums.
The matmuls are taken as ``G @ u^T``, so a pass over one axis also
transposes the plane: the same stage applied twice gives ``F x F`` in
natural orientation.  Row k2 of the k1-th
product lands at row k1 + r k2 by a stride-r sublane store, so the
output is in natural k order, exactly what ``jnp.fft`` gives.

Centring: for N/2 even, fftshift . DFT . ifftshift = C DFT C with
C_n = (-1)^n.  (-1)^n = (-1)^n2 and (-1)^k = (-1)^k1 (-1)^(r k2) ride in
G_k1, together with the 1/sqrt(N) of the ortho norm.  The matrices are
made once per plan on the host in float64 (:func:`dft_constants`); the
r-point coefficients are compile-time floats.

One grid step holds one (N, N) plane in VMEM (re and im: in, out, the
transposed intermediate and the stage-2 result): one HBM read and one
write per transform.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
RADICES = (1, 2, 3, 4, 6, 8)
"""The r of N = r * 128 the kernel takes (N = 128 .. 1024)."""


@functools.lru_cache(maxsize=None)
def dft_constants(n: int, inverse: bool, centered: bool) -> np.ndarray:
    """The (r, 256, 256) float32 matrices G_k1 (twiddles, centring signs
    and norm folded in), rounded once from float64; made once per
    geometry and direction."""
    r = n // LANES
    sign = 1.0 if inverse else -1.0
    k2 = np.arange(LANES)[None, :, None]
    n2 = np.arange(LANES)[None, None, :]
    k1 = np.arange(r)[:, None, None]
    f = np.exp(sign * 2j * np.pi * (k2 * n2 / LANES + k1 * n2 / n))
    f = f / math.sqrt(n)
    if centered:
        f = f * (-1.0) ** (n2 + k1 + r * k2)
    a, b = f.real, f.imag
    g = np.concatenate([np.concatenate([a, -b], axis=2),
                        np.concatenate([b, a], axis=2)], axis=1)
    return g.astype(np.float32)


def _coefficients(r: int, inverse: bool):
    """Wr^m as (re, im) floats for m < r, rounded to exact 0 / +-1."""
    sign = 1.0 if inverse else -1.0
    out = []
    for m in range(r):
        z = complex(np.exp(sign * 2j * np.pi * m / r))
        out.append(tuple(round(v) if abs(v - round(v)) < 1e-12 else v
                         for v in (z.real, z.imag)))
    return out


def _rsum(xs, k1: int, coef):
    """Sum_n1 Wr^(n1 k1) x_n1 over (re, im) blocks: inputs with one
    exponent are added first, conjugate exponents m and r - m share
    their multiplies."""
    r = len(xs)
    groups: dict[int, tuple] = {}
    for n1, x in enumerate(xs):
        m = (n1 * k1) % r
        g = groups.get(m)
        groups[m] = x if g is None else (g[0] + x[0], g[1] + x[1])
    acc_r = acc_i = None

    def add(tr, ti):
        nonlocal acc_r, acc_i
        acc_r = tr if acc_r is None else acc_r + tr
        acc_i = ti if acc_i is None else acc_i + ti

    for m in sorted(groups):
        s = groups[m]
        cr, ci = coef[m]
        if (cr, ci) == (1, 0):
            add(*s)
        elif (cr, ci) == (-1, 0):
            add(-s[0], -s[1])
        elif (cr, ci) == (0, 1):
            add(-s[1], s[0])
        elif (cr, ci) == (0, -1):
            add(s[1], -s[0])
        elif r - m in groups and m < r - m:
            # c S_m + conj(c) S_(r-m) = c_r (S_m + S_(r-m)) + i c_i (S_m - S_(r-m))
            t = groups[r - m]
            add(cr * (s[0] + t[0]) - ci * (s[1] - t[1]),
                cr * (s[1] + t[1]) + ci * (s[0] - t[0]))
        elif r - m in groups:
            continue                      # taken with its conjugate
        else:
            add(cr * s[0] - ci * s[1], cr * s[1] + ci * s[0])
    return acc_r, acc_i


def _nt(a, b):
    """a @ b^T at full f32 precision: (256, 128) x (rows, 128) -> (256, rows)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _stage(read, dst_r, dst_i, g, *, n, r, coef, br):
    """dst = (src F)^T over one (N, N) plane: the transform of src's
    lane axis, written transposed, in natural k order, ``br`` rows of
    src at a time.  ``read(m, rows)`` gives src's lane block m of those
    rows as (re, im); dst is stored as its r lane blocks, (r, N, 128),
    since a strided store needs a base of 128 lanes."""
    nb = br // LANES

    def chunk(c, carry):
        xs = [read(m, pl.ds(pl.multiple_of(c * br, br), br))
              for m in range(r)]
        for k1 in range(r):
            ur, ui = _rsum(xs, k1, coef)
            # G_k1 @ [ur | ui]^T: (256, 256) x (br, 256) -> (256, br),
            # as two 128-term sums (half the rounding of one of 256)
            v = _nt(g[k1, :, :LANES], ur) + _nt(g[k1, :, LANES:], ui)
            at = pl.ds(k1, LANES, stride=r) if r > 1 else slice(0, LANES)
            for j in range(nb):
                cols = slice(LANES * j, LANES * (j + 1))
                dst_r[c * nb + j, at, :] = v[:LANES, cols]
                dst_i[c * nb + j, at, :] = v[LANES:, cols]
        return carry

    lax.fori_loop(0, n // br, chunk, 0)


def _dft2_kernel(xr, xi, g, or_, oi, tr, ti, sr, si, *, r, coef, br):
    n = xr.shape[0]
    kw = dict(n=n, r=r, coef=coef, br=br)
    lanes = lambda m: slice(LANES * m, LANES * (m + 1))
    _stage(lambda m, rows: (xr[rows, lanes(m)], xi[rows, lanes(m)]),
           tr, ti, g, **kw)
    _stage(lambda m, rows: (tr[m, rows, :], ti[m, rows, :]),
           sr, si, g, **kw)
    for m in range(r):
        or_[:, lanes(m)] = sr[m]
        oi[:, lanes(m)] = si[m]


@functools.partial(jax.jit, static_argnames=("inverse", "br", "interpret"))
def dft2c_pallas(xr, xi, g, *, inverse=False, br=256, interpret=True):
    """2-D DFT of a (B, N, N) stack of re/im f32 planes with the matrices
    of :func:`dft_constants` (which carry the direction, the centring
    and the norm); ``inverse`` picks the r-point coefficients' sign.
    ``br`` rows of a plane per matmul (a multiple of 128, cut to the
    largest such that divides N)."""
    B, N, N2 = xr.shape
    r = N // LANES
    assert N == N2 and r * LANES == N and r in RADICES, (N, N2)
    assert g.shape == (r, 2 * LANES, 2 * LANES), g.shape
    assert br % LANES == 0, br
    br = LANES * math.gcd(br // LANES, r)
    plane = pl.BlockSpec((pl.squeezed, N, N), lambda b: (b, 0, 0))
    whole = pl.BlockSpec(g.shape, lambda b: (0, 0, 0))
    # in and out double-buffered, the transposed and the final plane,
    # the matrices, and the values of one chunk the compiler keeps
    vmem = 4 * (12 * N * N + 2 * g.size + 8 * br * N) + (4 << 20)
    return pl.pallas_call(
        functools.partial(_dft2_kernel, r=r, br=br,
                          coef=tuple(_coefficients(r, inverse))),
        grid=(B,),
        in_specs=[plane, plane, whole],
        out_specs=[plane, plane],
        out_shape=[jax.ShapeDtypeStruct((B, N, N), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((r, N, LANES), jnp.float32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=min(vmem, 100 << 20)),
        interpret=interpret,
    )(xr, xi, g)

"""Coil-sensitivity pointwise ops as Pallas TPU kernels.

The paper maps single pixels to GPU threads for these ops ("custom CUDA
kernels handle the point-wise operations", §3.2).  The TPU shape: tile
the image plane into VMEM rows and run the complex arithmetic on the
VPU.  Complex values travel as separate re/im planes — (X, Y) f32 arrays
tile the (8,128) VREG lanes natively, unlike an interleaved (...,2)
layout.

  coil_forward: grid (J, X/bx)          z_j = c_j * x
  coil_adjoint: grid (X/bx, J) with J the sequential `arbitrary` axis —
                the Sum_j accumulates in VMEM scratch (one pass over the
                channel dim, fused with the M_Omega mask: the arithmetic
                half of the paper's kern_all_red_p2p_2d).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fwd_kernel(cr, ci, xr, xi, zr, zi):
    a, b = cr[0], ci[0]
    c, d = xr[...], xi[...]
    zr[0] = a * c - b * d
    zi[0] = a * d + b * c


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def coil_forward_pallas(cr, ci, xr, xi, *, bx=32, interpret=True):
    J, X, Y = cr.shape
    bx = min(bx, X)
    assert X % bx == 0
    grid = (J, X // bx)
    return pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bx, Y), lambda j, i: (j, i, 0)),
            pl.BlockSpec((1, bx, Y), lambda j, i: (j, i, 0)),
            pl.BlockSpec((bx, Y), lambda j, i: (i, 0)),
            pl.BlockSpec((bx, Y), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bx, Y), lambda j, i: (j, i, 0)),
            pl.BlockSpec((1, bx, Y), lambda j, i: (j, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((J, X, Y), cr.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(cr, ci, xr, xi)


def _lincomb_kernel(ar, ai, xr, xi, br, bi, yr, yi, s, zr, zi):
    # out_j = s * (a*x_j + b*y_j): one VMEM pass over both coil stacks
    a, b = ar[...], ai[...]
    c, d = xr[0], xi[0]
    e, f = br[...], bi[...]
    g, h = yr[0], yi[0]
    re = a * c - b * d + e * g - f * h
    im = a * d + b * c + e * h + f * g
    zr[0] = s[...] * re
    zi[0] = s[...] * im


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def coil_lincomb_pallas(ar, ai, xr, xi, br, bi, yr, yi, s, *,
                        bx=32, interpret=True):
    """out_j = s * (a*x_j + b*y_j); planes (X, Y), stacks (J, X, Y)."""
    J, X, Y = xr.shape
    bx = min(bx, X)
    assert X % bx == 0
    grid = (J, X // bx)
    plane = pl.BlockSpec((bx, Y), lambda j, i: (i, 0))
    stack = pl.BlockSpec((1, bx, Y), lambda j, i: (j, i, 0))
    return pl.pallas_call(
        _lincomb_kernel,
        grid=grid,
        in_specs=[plane, plane, stack, stack,
                  plane, plane, stack, stack, plane],
        out_specs=[stack, stack],
        out_shape=[jax.ShapeDtypeStruct((J, X, Y), xr.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(ar, ai, xr, xi, br, bi, yr, yi, s)


def _scale_mult_kernel(ar, ai, xr, xi, s, zr, zi):
    # out_j = s * (a * x_j): the one-term lincomb (G's fov*(rho*c))
    a, b = ar[...], ai[...]
    c, d = xr[0], xi[0]
    zr[0] = s[...] * (a * c - b * d)
    zi[0] = s[...] * (a * d + b * c)


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def coil_scale_mult_pallas(ar, ai, xr, xi, s, *, bx=32, interpret=True):
    """out_j = s * (a * x_j) — coil_lincomb's one-term form, its own
    kernel so the b=None case pays no zero-operand traffic."""
    J, X, Y = xr.shape
    bx = min(bx, X)
    assert X % bx == 0
    grid = (J, X // bx)
    plane = pl.BlockSpec((bx, Y), lambda j, i: (i, 0))
    stack = pl.BlockSpec((1, bx, Y), lambda j, i: (j, i, 0))
    return pl.pallas_call(
        _scale_mult_kernel,
        grid=grid,
        in_specs=[plane, plane, stack, stack, plane],
        out_specs=[stack, stack],
        out_shape=[jax.ShapeDtypeStruct((J, X, Y), xr.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(ar, ai, xr, xi, s)


def _plane_mult_kernel(zr, zi, m, outr, outi):
    outr[0] = zr[0] * m[...]
    outi[0] = zi[0] * m[...]


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def plane_mult_pallas(zr, zi, m, *, bx=32, interpret=True):
    """out_j = z_j * m (real plane broadcast over the coil dim)."""
    J, X, Y = zr.shape
    bx = min(bx, X)
    assert X % bx == 0
    grid = (J, X // bx)
    plane = pl.BlockSpec((bx, Y), lambda j, i: (i, 0))
    stack = pl.BlockSpec((1, bx, Y), lambda j, i: (j, i, 0))
    return pl.pallas_call(
        _plane_mult_kernel,
        grid=grid,
        in_specs=[stack, stack, plane],
        out_specs=[stack, stack],
        out_shape=[jax.ShapeDtypeStruct((J, X, Y), zr.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(zr, zi, m)


def _adj_kernel(cr, ci, zr, zi, m, outr, outi, accr, acci, *, nj):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        accr[...] = jnp.zeros_like(accr)
        acci[...] = jnp.zeros_like(acci)

    a, b = cr[0], ci[0]                      # conj(c) = a - ib
    c, d = zr[0], zi[0]
    accr[...] += a * c + b * d
    acci[...] += a * d - b * c

    @pl.when(j == nj - 1)
    def _final():
        outr[...] = accr[...] * m[...]
        outi[...] = acci[...] * m[...]


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def coil_adjoint_pallas(cr, ci, zr, zi, mask, *, bx=32, interpret=True):
    J, X, Y = cr.shape
    bx = min(bx, X)
    assert X % bx == 0
    grid = (X // bx, J)
    kern = functools.partial(_adj_kernel, nj=J)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bx, Y), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, bx, Y), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, bx, Y), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, bx, Y), lambda i, j: (j, i, 0)),
            pl.BlockSpec((bx, Y), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bx, Y), lambda i, j: (i, 0)),
            pl.BlockSpec((bx, Y), lambda i, j: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((X, Y), cr.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bx, Y), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cr, ci, zr, zi, mask)

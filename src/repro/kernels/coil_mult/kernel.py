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


# -- simultaneous multi-slice: the partition mixing fused into the chains --
#
# ``mix`` is the static (Q, M) partition-encoding matrix as nested tuples
# of (re, im) floats; it is baked into the kernel, so an entry of exactly
# 1 costs no multiply.  Slices and partitions ride a leading block dim:
# one grid step reads every slice of one coil channel's row block once
# and writes every partition of it once.

def _cscale(c, re, im):
    """(re + i im) * c for a complex constant and a (real, imag) pair."""
    if (re, im) == (1.0, 0.0):
        return c
    return re * c[0] - im * c[1], re * c[1] + im * c[0]


def _cmix(row, terms):
    """Sum_k row[k] * terms[k] over (real, imag) pairs."""
    acc_r = acc_i = None
    for (re, im), t in zip(row, terms):
        if (re, im) == (0.0, 0.0):
            continue
        tr, ti = _cscale(t, re, im)
        acc_r = tr if acc_r is None else acc_r + tr
        acc_i = ti if acc_i is None else acc_i + ti
    return acc_r, acc_i


def _sms_lincomb_kernel(*refs, mix, two):
    if two:
        ar, ai, xr, xi, br, bi, yr, yi, s, zr, zi = refs
    else:
        ar, ai, xr, xi, s, zr, zi = refs
    terms = []
    for m in range(len(mix[0])):
        a, b, c, d = ar[m], ai[m], xr[m, 0], xi[m, 0]
        re, im = a * c - b * d, a * d + b * c
        if two:
            e, f, g, h = br[m], bi[m], yr[m, 0], yi[m, 0]
            re, im = re + e * g - f * h, im + e * h + f * g
        terms.append((re, im))
    for q, row in enumerate(mix):
        re, im = _cmix(row, terms)
        zr[q, 0] = s[...] * re
        zi[q, 0] = s[...] * im


@functools.partial(jax.jit, static_argnames=("mix", "bx", "interpret"))
def sms_lincomb_pallas(ar, ai, xr, xi, br, bi, yr, yi, s, *, mix,
                       bx=32, interpret=True):
    """out_qj = s * Sum_m mix[q][m] (a_m x_mj + b_m y_mj); planes (M, X,
    Y), stacks (M, J, X, Y), ``s`` (X, Y); ``br is None`` drops the
    second term (G's one-term form)."""
    M, J, X, Y = xr.shape
    Q = len(mix)
    bx = min(bx, X)
    assert X % bx == 0
    two = br is not None
    # rows outer, channels inner: the slice planes' block index does not
    # change along j, so they are fetched once per row block
    grid = (X // bx, J)
    plane = pl.BlockSpec((M, bx, Y), lambda i, j: (0, i, 0))
    stack = pl.BlockSpec((M, 1, bx, Y), lambda i, j: (0, j, i, 0))
    out = pl.BlockSpec((Q, 1, bx, Y), lambda i, j: (0, j, i, 0))
    one = pl.BlockSpec((bx, Y), lambda i, j: (i, 0))
    args = (ar, ai, xr, xi) + ((br, bi, yr, yi) if two else ()) + (s,)
    specs = [plane, plane, stack, stack] + (
        [plane, plane, stack, stack] if two else []) + [one]
    return pl.pallas_call(
        functools.partial(_sms_lincomb_kernel, mix=mix, two=two),
        grid=grid,
        in_specs=specs,
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((Q, J, X, Y), xr.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*args)


def _sms_adj_kernel(vr, vi, cr, ci, s, gr, gi, pr, pi, wr, wi, accr, acci,
                    *, mix, nj):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        accr[...] = jnp.zeros_like(accr)
        acci[...] = jnp.zeros_like(acci)

    parts = [(vr[q, 0], vi[q, 0]) for q in range(len(mix))]
    for m in range(len(mix[0])):
        # z_m = s * Sum_q conj(mix[q][m]) v_q
        zr, zi = _cmix([(mix[q][m][0], -mix[q][m][1])
                        for q in range(len(mix))], parts)
        zr, zi = s[...] * zr, s[...] * zi
        a, b = cr[m, 0], ci[m, 0]               # conj(c) = a - ib
        accr[m] += a * zr + b * zi
        acci[m] += a * zi - b * zr
        e, f = gr[m], gi[m]
        wr[m, 0] = e * zr - f * zi
        wi[m, 0] = e * zi + f * zr

    @pl.when(j == nj - 1)
    def _final():
        pr[...] = accr[...]
        pi[...] = acci[...]


@functools.partial(jax.jit, static_argnames=("mix", "bx", "interpret"))
def sms_adjoint_pallas(vr, vi, cr, ci, s, gr, gi, *, mix, bx=32,
                       interpret=True):
    """z_mj = s * Sum_q conj(mix[q][m]) v_qj; returns the channel sum
    Sum_j conj(c_mj) z_mj (M, X, Y) and g_m z_mj (M, J, X, Y) as re/im
    planes.  J is the sequential axis the sum accumulates over in VMEM
    scratch, as in :func:`coil_adjoint_pallas`."""
    Q, J, X, Y = vr.shape
    M = cr.shape[0]
    bx = min(bx, X)
    assert X % bx == 0
    grid = (X // bx, J)
    vst = pl.BlockSpec((Q, 1, bx, Y), lambda i, j: (0, j, i, 0))
    cst = pl.BlockSpec((M, 1, bx, Y), lambda i, j: (0, j, i, 0))
    plane = pl.BlockSpec((M, bx, Y), lambda i, j: (0, i, 0))
    one = pl.BlockSpec((bx, Y), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_sms_adj_kernel, mix=mix, nj=J),
        grid=grid,
        in_specs=[vst, vst, cst, cst, one, plane, plane],
        out_specs=[plane, plane, cst, cst],
        out_shape=[jax.ShapeDtypeStruct((M, X, Y), vr.dtype)] * 2
        + [jax.ShapeDtypeStruct((M, J, X, Y), vr.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((M, bx, Y), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(vr, vi, cr, ci, s, gr, gi)

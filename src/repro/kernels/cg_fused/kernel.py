"""Fused CG vector updates as Pallas TPU kernels.

The 2017 follow-up ("Accelerated Computing in MRI", Schaetz et al.)
attributes a large share of its real-time NLINV win to fusing the CG
pointwise/vector chains into single kernels.  The TPU shape of that
optimization: one pass over VMEM-resident row tiles performs both vector
updates AND accumulates the dot-product epilogue in scratch, instead of
three separate passes (axpy, axpy, dot) over HBM.

Complex values travel as separate re/im planes — (M, Y) f32 arrays tile
the (8,128) VREG lanes natively (same convention as ``coil_mult`` /
``gridding``).  The iterate pytree's leaves are flattened to (M, Y) by
``ops.py``; the grid walks row blocks sequentially (``arbitrary``) so
the scalar epilogue accumulates across blocks in SMEM scratch.

Scalars (``alpha``/``beta`` in, ``rs``/``d`` out) are (1, 1) SMEM
arrays: under ``jax.vmap`` the batch becomes a leading grid dim and the
block keeps the array's last two dims, the form Mosaic's tiling rule
accepts (a (1,) scalar would become a (B, 1) array with an untileable
(1, 1) block of it).

  cg_update: x' = x + a*p, r' = r - a*Ap, rs = sum |r'|^2
  xpby_dot:  w  = x + b*y,                d  = sum |w|^2
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


_SCALAR = jax.ShapeDtypeStruct((1, 1), jnp.float32)


def _cg_update_kernel(alpha, pr, pi, apr, api, xr, xi, rr, ri,
                      xro, xio, rro, rio, rso, acc, *, nblk):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[0, 0] = 0.0

    a = alpha[0, 0]
    xro[...] = xr[...] + a * pr[...]
    xio[...] = xi[...] + a * pi[...]
    r2r = rr[...] - a * apr[...]
    r2i = ri[...] - a * api[...]
    rro[...] = r2r
    rio[...] = r2i
    acc[0, 0] += jnp.sum(r2r * r2r) + jnp.sum(r2i * r2i)

    @pl.when(i == nblk - 1)
    def _final():
        rso[0, 0] = acc[0, 0]


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def cg_update_pallas(alpha, pr, pi, apr, api, xr, xi, rr, ri, *,
                     bm=32, interpret=True):
    """Planes are (M, Y) f32; ``alpha`` is a (1, 1) f32 array (SMEM).
    Returns (xr', xi', rr', ri', rs) with ``rs`` a (1, 1) f32."""
    M, Y = pr.shape
    bm = min(bm, M)
    assert M % bm == 0
    nblk = M // bm
    row = pl.BlockSpec((bm, Y), lambda i: (i, 0))
    kern = functools.partial(_cg_update_kernel, nblk=nblk)
    return pl.pallas_call(
        kern,
        grid=(nblk,),
        in_specs=[_scalar_spec()] + [row] * 8,
        out_specs=[row] * 4 + [_scalar_spec()],
        out_shape=[jax.ShapeDtypeStruct((M, Y), pr.dtype)] * 4 +
                  [_SCALAR],
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(alpha, pr, pi, apr, api, xr, xi, rr, ri)


def _xpby_kernel(beta, xr, xi, yr, yi, wro, wio):
    b = beta[0, 0]
    wro[...] = xr[...] + b * yr[...]
    wio[...] = xi[...] + b * yi[...]


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def xpby_pallas(beta, xr, xi, yr, yi, *, bm=32, interpret=True):
    """``w = x + b*y`` without the dot epilogue — the CG search-direction
    step, whose epilogue the solver discards (an opaque pallas_call is
    not DCE-able, so the no-epilogue form is its own kernel)."""
    M, Y = xr.shape
    bm = min(bm, M)
    assert M % bm == 0
    row = pl.BlockSpec((bm, Y), lambda i: (i, 0))
    return pl.pallas_call(
        _xpby_kernel,
        grid=(M // bm,),
        in_specs=[_scalar_spec()] + [row] * 4,
        out_specs=[row] * 2,
        out_shape=[jax.ShapeDtypeStruct((M, Y), xr.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(beta, xr, xi, yr, yi)


def _xpby_dot_kernel(beta, xr, xi, yr, yi, wro, wio, do, acc, *, nblk):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc[0, 0] = 0.0

    b = beta[0, 0]
    wr = xr[...] + b * yr[...]
    wi = xi[...] + b * yi[...]
    wro[...] = wr
    wio[...] = wi
    acc[0, 0] += jnp.sum(wr * wr) + jnp.sum(wi * wi)

    @pl.when(i == nblk - 1)
    def _final():
        do[0, 0] = acc[0, 0]


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def xpby_dot_pallas(beta, xr, xi, yr, yi, *, bm=32, interpret=True):
    """Planes are (M, Y) f32; ``beta`` is a (1, 1) f32 array (SMEM).
    Returns (wr, wi, d) with ``d`` a (1, 1) f32."""
    M, Y = xr.shape
    bm = min(bm, M)
    assert M % bm == 0
    nblk = M // bm
    row = pl.BlockSpec((bm, Y), lambda i: (i, 0))
    kern = functools.partial(_xpby_dot_kernel, nblk=nblk)
    return pl.pallas_call(
        kern,
        grid=(nblk,),
        in_specs=[_scalar_spec()] + [row] * 4,
        out_specs=[row] * 2 + [_scalar_spec()],
        out_shape=[jax.ShapeDtypeStruct((M, Y), xr.dtype)] * 2 +
                  [_SCALAR],
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(beta, xr, xi, yr, yi)

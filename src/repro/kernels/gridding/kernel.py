"""Radial gridding/degridding as Pallas TPU kernels.

The GPU formulation of the paper era scatters each sample with atomics;
TPUs have no atomics, so the plan layer factors the bilinear
interpolation into *separable dense matrices* ``Ax (S, X)`` / ``Ay (S,
Y)`` (two nonzeros per row, built once per trajectory at plan-build
time) and the kernels become MXU matmuls:

  degrid:  out[j, s] = sum_v (Ax @ g_j)[s, v] * Ay[s, v]
  grid:    g_j       = Ax^T @ (y_j[:, None] * Ay)       (exact adjoint)

Complex data travels as separate re/im planes — (.., Y) f32 arrays tile
the (8, 128) VREG lanes natively.  The sample dim is tiled in blocks of
``bs``; ``grid`` accumulates over sample blocks in VMEM scratch (the
sequential ``arbitrary`` grid axis), mirroring the coil_adjoint kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _degrid_kernel(ax, ay, gr, gi, outr, outi):
    a = ax[...]                              # (bs, X)
    tr = jnp.dot(a, gr[0], preferred_element_type=jnp.float32)   # (bs, Y)
    ti = jnp.dot(a, gi[0], preferred_element_type=jnp.float32)
    w = ay[...]                              # (bs, Y)
    outr[0] = jnp.sum(tr * w, axis=1)
    outi[0] = jnp.sum(ti * w, axis=1)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def degrid_pallas(ax, ay, gr, gi, *, bs=128, interpret=True):
    """Sample the grid at the trajectory.  ax: (S, X), ay: (S, Y),
    gr/gi: (J, X, Y) f32 -> (J, S) f32 re/im.  S must tile by ``bs``."""
    S, X = ax.shape
    Y = ay.shape[1]
    J = gr.shape[0]
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    return pl.pallas_call(
        _degrid_kernel,
        grid=(J, S // bs),
        in_specs=[
            pl.BlockSpec((bs, X), lambda j, s: (s, 0)),
            pl.BlockSpec((bs, Y), lambda j, s: (s, 0)),
            pl.BlockSpec((1, X, Y), lambda j, s: (j, 0, 0)),
            pl.BlockSpec((1, X, Y), lambda j, s: (j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs), lambda j, s: (j, s)),
            pl.BlockSpec((1, bs), lambda j, s: (j, s)),
        ],
        out_shape=[jax.ShapeDtypeStruct((J, S), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(ax, ay, gr, gi)


def _grid_kernel(ax, ay, yr, yi, outr, outi, accr, acci, *, ns):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        accr[...] = jnp.zeros_like(accr)
        acci[...] = jnp.zeros_like(acci)

    w = ay[...]                              # (bs, Y)
    at = ax[...].T                           # (X, bs)
    accr[...] += jnp.dot(at, yr[0][:, None] * w,
                         preferred_element_type=jnp.float32)
    acci[...] += jnp.dot(at, yi[0][:, None] * w,
                         preferred_element_type=jnp.float32)

    @pl.when(s == ns - 1)
    def _final():
        outr[0] = accr[...]
        outi[0] = acci[...]


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def grid_pallas(ax, ay, yr, yi, *, bs=128, interpret=True):
    """Adjoint: scatter samples onto the grid.  yr/yi: (J, S) f32 ->
    (J, X, Y) f32 re/im, accumulated over sample blocks in VMEM."""
    S, X = ax.shape
    Y = ay.shape[1]
    J = yr.shape[0]
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    kern = functools.partial(_grid_kernel, ns=S // bs)
    return pl.pallas_call(
        kern,
        grid=(J, S // bs),
        in_specs=[
            pl.BlockSpec((bs, X), lambda j, s: (s, 0)),
            pl.BlockSpec((bs, Y), lambda j, s: (s, 0)),
            pl.BlockSpec((1, bs), lambda j, s: (j, s)),
            pl.BlockSpec((1, bs), lambda j, s: (j, s)),
        ],
        out_specs=[
            pl.BlockSpec((1, X, Y), lambda j, s: (j, 0, 0)),
            pl.BlockSpec((1, X, Y), lambda j, s: (j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((J, X, Y), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((X, Y), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ax, ay, yr, yi)

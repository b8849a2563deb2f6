"""Masked G-way partial sum as a Pallas TPU kernel — the compute half of
the paper's ``kern_all_red_p2p_2d``.

The CUDA original has each GPU read its 3 peers' buffers over PCIe P2P
and sum 4 pointers inside one kernel, masking to the 2-D section that
M_Omega keeps.  TPUs expose no cross-chip loads at this level, so the
transport is a shard_map psum (ICI) — see ops.masked_psum_crop — and
this kernel fuses what remains local: sum the G gathered partials + mask
in one VMEM pass (instead of G adds + 1 mask kernel = 2x HBM traffic).

  grid (X/bx,): block (G, bx, Y) re/im in VMEM, sum over axis 0 on VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pr, pi, m, outr, outi):
    outr[...] = jnp.sum(pr[...], axis=0) * m[...]
    outi[...] = jnp.sum(pi[...], axis=0) * m[...]


@functools.partial(jax.jit, static_argnames=("bx", "interpret"))
def masked_sum_pallas(pr, pi, mask, *, bx=32, interpret=True):
    G, X, Y = pr.shape
    bx = min(bx, X)
    assert X % bx == 0
    return pl.pallas_call(
        _kernel,
        grid=(X // bx,),
        in_specs=[
            pl.BlockSpec((G, bx, Y), lambda i: (0, i, 0)),
            pl.BlockSpec((G, bx, Y), lambda i: (0, i, 0)),
            pl.BlockSpec((bx, Y), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bx, Y), lambda i: (i, 0)),
            pl.BlockSpec((bx, Y), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((X, Y), pr.dtype)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(pr, pi, mask)

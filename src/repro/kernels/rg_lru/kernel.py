"""RG-LRU linear recurrence as a Pallas TPU kernel.

Blocked over (batch, width); the time dimension is the trailing
`arbitrary` grid axis, so the carried state h lives in VMEM scratch
across time-chunks.  Inside a chunk, a fori_loop walks the bs time steps
on VPU registers — elementwise FMA, no MXU.  This is the TPU-native shape
of the scan: HBM traffic is exactly one read of (log_a, b) and one write
of h per element, which is the roofline floor for a first-order
recurrence.

  grid = (B/bb, W/bw, S/bs)   semantics (parallel, parallel, arbitrary)
  blocks: (bb, bs, bw) in VMEM; scratch h (bb, bw) f32
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(log_a_ref, b_ref, h0_ref, h_ref, hlast_ref, hs_ref, *,
            bs: int, ns: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        hs_ref[...] = h0_ref[...].astype(jnp.float32)

    def step(t, h):
        a = jnp.exp(log_a_ref[:, t, :].astype(jnp.float32))
        h = a * h + b_ref[:, t, :].astype(jnp.float32)
        h_ref[:, t, :] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, step, hs_ref[...])
    hs_ref[...] = h

    @pl.when(si == ns - 1)
    def _final():
        hlast_ref[...] = h.astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bb", "bw", "bs", "interpret"))
def rg_lru_pallas(log_a, b, h0, *, bb=8, bw=128, bs=256, interpret=True):
    B, S, W = b.shape
    bb, bw, bs = min(bb, B), min(bw, W), min(bs, S)
    assert B % bb == 0 and W % bw == 0 and S % bs == 0
    ns = S // bs
    grid = (B // bb, W // bw, ns)
    kernel = functools.partial(_kernel, bs=bs, ns=ns)
    h, hlast = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bs, bw), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((bb, bs, bw), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((bb, bw), lambda i, j, t: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bs, bw), lambda i, j, t: (i, t, j)),
            pl.BlockSpec((bb, bw), lambda i, j, t: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), b.dtype),
            jax.ShapeDtypeStruct((B, W), b.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bb, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b, h0)
    return h, hlast

"""Flash attention as a Pallas TPU kernel.

TPU-native design (NOT a CUDA port): the grid's innermost dimension
iterates KV blocks *sequentially* per core (TPU grids are sequential over
the trailing `arbitrary` dimension), so the online-softmax running state
(m, l, acc) lives in VMEM scratch that persists across KV steps — the TPU
analogue of a CUDA thread-block's shared-memory accumulator, but sized to
VMEM and MXU tiles:

  grid = (B, Hq, nQ, nK)        semantics (parallel, parallel, parallel, arbitrary)
  q block   (1, 1, bq, D)       VMEM, MXU-aligned bq, D multiples of 128
  k/v block (1, 1, bk, D)       indexed by kv head = q head // group
  scratch   acc (bq, D) f32, m/l (bq, 128) f32

Causal + sliding-window blocks that are fully masked are skipped via
``pl.when`` (no MXU work), which is what makes the causal kernel ~2x
cheaper — block-level skipping replaces CUDA's early-exit warps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref,
               acc_ref, m_ref, l_ref, *,
               bq: int, bk: int, nk: int, causal: bool,
               window: int | None, softcap: float | None,
               q_offset: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # absolute positions of this q/k block
    q_lo = q_offset + qi * bq
    k_lo = ki * bk

    # block-level skip: block is live unless fully masked
    live = True
    if causal:
        live = jnp.asarray(k_lo <= q_lo + bq - 1)
    if window is not None:
        live = jnp.logical_and(live, (q_lo - (k_lo + bk - 1)) < window)
    live = jnp.logical_and(live, k_lo < kv_len_ref[0])

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < kv_len_ref[0]
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, (q_pos - k_pos) < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                                # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)                     # rescale old acc
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_cur

    @pl.when(ki == nk - 1)
    def _final():
        l = jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "q_offset",
                              "scale", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, kv_len=None, *, causal=True, window=None,
                           softcap=None, q_offset=0, scale=None,
                           bq=128, bk=128, interpret=True):
    """q: (B, Hq, S, D); k, v: (B, Hkv, T, D).  S % bq == 0, T % bk == 0.

    ``interpret=True`` runs the kernel body on CPU for validation; on a
    real TPU backend pass ``interpret=False``.
    """
    B, Hq, S, D = q.shape
    _, Hkv, T, _ = k.shape
    Dv = v.shape[-1]
    g = Hq // Hkv
    scale = float(1.0 / np.sqrt(D)) if scale is None else float(scale)
    bq = min(bq, S)
    bk = min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    nq, nk = S // bq, T // bk
    kv_len = jnp.full((1,), T if kv_len is None else kv_len, jnp.int32)

    kernel = functools.partial(
        _fa_kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        softcap=softcap, q_offset=q_offset, scale=scale)

    grid = (B, Hq, nq, nk)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, *_: (b, h // g, j, 0)),
                pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j, *_: (b, h // g, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, Dv),
                                   lambda b, h, i, j, *_: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, Dv), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(kv_len, q, k, v)

"""Chunkwise mLSTM as a Pallas TPU kernel.

Same factorization as ops.mlstm_chunkwise, with the inter-chunk state
(C, n, m) carried in VMEM scratch across the sequential chunk grid axis.
Intra-chunk work is three MXU matmuls (q k^T, scores v, D k); the decay
matrix D is built on VPU from cumulative log-gates.

  grid = (B, H, S/L)            semantics (parallel, parallel, arbitrary)
  blocks: q,k (1,1,L,dk)  v (1,1,L,dv)  gates (1,1,L)
  scratch: C (dk, dv) f32, n (1, dk) f32, m (1, 1) f32
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, li_ref, lf_ref,
            h_ref, Cout_ref, nout_ref, mout_ref,
            C_ref, n_ref, m_ref, *, L: int, nc: int, dk: int, dv: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    q = q_ref[0, 0].astype(jnp.float32) * (dk ** -0.5)     # (L, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    li = li_ref[0, 0].astype(jnp.float32)                  # (L,)
    lf = lf_ref[0, 0].astype(jnp.float32)
    C, n, m = C_ref[...], n_ref[0], m_ref[0, 0]

    c = jnp.cumsum(lf)                                     # (L,)
    W = c[:, None] - c[None, :] + li[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    W = jnp.where(tri, W, NEG)
    m_intra = jnp.max(W, axis=1)
    m_inter = c + m
    m_t = jnp.maximum(m_intra, m_inter)
    D = jnp.exp(W - m_t[:, None])
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * D
    h_num = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    h_num += jnp.exp(m_inter - m_t)[:, None] * jax.lax.dot_general(
        q, C, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    n_t = jax.lax.dot_general(D, k, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    n_t += jnp.exp(m_inter - m_t)[:, None] * n[None, :]
    den = jnp.maximum(jnp.abs(jnp.sum(q * n_t, axis=1)), jnp.exp(-m_t))
    h_ref[0, 0] = (h_num / den[:, None]).astype(h_ref.dtype)

    # -- state hand-off
    cL = c[L - 1]
    w_out = cL - c + li
    m_new = jnp.maximum(cL + m, jnp.max(w_out))
    wk = jnp.exp(w_out - m_new)
    C_new = jnp.exp(cL + m - m_new) * C + jax.lax.dot_general(
        k * wk[:, None], v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_new = jnp.exp(cL + m - m_new) * n + jnp.sum(k * wk[:, None], axis=0)
    C_ref[...] = C_new
    n_ref[0] = n_new
    m_ref[0, 0] = m_new

    @pl.when(ci == nc - 1)
    def _final():
        Cout_ref[0, 0] = C_new
        nout_ref[0, 0] = n_new
        mout_ref[0, 0] = m_new.reshape(1)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_pallas(q, k, v, log_i, log_f, state=None, *, chunk=128,
                 interpret=True):
    B, H, S, dk = q.shape
    dv = v.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L
    if state is not None and any(
            jnp.any(jnp.asarray(s) != 0) for s in jax.tree.leaves(state)):
        raise NotImplementedError(
            "mlstm_pallas starts from zero state; fold prior state via ops")
    kernel = functools.partial(_kernel, L=L, nc=nc, dk=dk, dv=dv)
    grid = (B, H, nc)
    h, C, n, m = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, L, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, L), lambda b, h, c: (b, h, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, dk, dv), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, h, c: (b, h, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, h, c: (b, h, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dv), v.dtype),
            jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dk), jnp.float32),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dk, dv), jnp.float32),
            pltpu.VMEM((1, dk), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, log_i, log_f)
    return h, (C, n, m[..., 0])

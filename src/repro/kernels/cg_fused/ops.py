"""Complex-array wrappers with registry dispatch for the fused CG steps.

On TPU the single-pass Pallas kernels run natively; elsewhere the ref
path is used directly (it is the same single-expression fusion, which
XLA compiles to one loop — interpret-mode Pallas would only slow the
hot path down).  Shapes are arbitrary: leaves are flattened to (M, Y)
row planes for the kernels and restored afterwards.  Backend routing,
the row-block eligibility rule, and the block-size choice all come
from the shared :mod:`repro.kernels.registry` specs below — the row
block ``bm`` lives in ONE place (``default_block``) instead of being
duplicated between this module and ``kernel.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import registry as kreg
from ..registry import KernelSpec, on_tpu, planes, rows_divisible
from .kernel import cg_update_pallas, xpby_dot_pallas, xpby_pallas
from .ref import cg_update_ref, xpby_dot_ref


def _cplx(key, shape):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


def _cg_update_case(keys, shape, alpha=0.37):
    p, ap, x, r = (_cplx(k, shape) for k in keys)
    a = jnp.float32(alpha)
    return (a, p, ap, x, r), {}, cg_update_ref(a, p, ap, x, r)


def _cg_update_samples(i):
    shape = [(32, 32), (4, 16, 48), (96, 128)][i]
    keys = jax.random.split(jax.random.PRNGKey(100 + i), 4)
    return _cg_update_case(keys, shape)


def _cg_update_shape_case(seed, m, y):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return _cg_update_case(keys, (m, y))


def _xpby_case(keys, shape, beta=0.61):
    x, y = (_cplx(k, shape) for k in keys)
    b = jnp.float32(beta)
    return (x, y, b), {}, xpby_dot_ref(x, y, b)


def _xpby_samples(i):
    shape = [(32, 48), (2, 32, 64)][i]
    keys = jax.random.split(jax.random.PRNGKey(200 + i), 2)
    return _xpby_case(keys, shape)


def _xpby_shape_case(seed, m, y):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return _xpby_case(keys, (m, y))


def _xpby_nodot_consistency(seed=0):
    """Property: the no-epilogue variant returns the identical ``w``
    (the separate kernel exists only because the opaque in-kernel dot
    cannot be DCE'd)."""
    args, _, _ = _xpby_samples(seed % 2)
    w_dot, d = xpby_dot(*args, impl="pallas")
    w_only, none = xpby_dot(*args, impl="pallas", with_dot=False)
    assert none is None and d is not None
    assert jnp.allclose(w_dot, w_only, atol=1e-6)


CG_UPDATE = kreg.register(KernelSpec(
    family="cg_fused", name="cg_update",
    pallas=cg_update_pallas, ref=cg_update_ref, fallback="jnp",
    block_args=("bm",), default_block=(32,),
    block_space=((8,), (16,), (32,), (64,), (128,)),
    supports=lambda block, alpha, p, ap, x, r, **kw:
        rows_divisible(p, block[0]),
    tol=1e-4,
    layout="complex leaves -> re/im (M, Y) f32 row planes, bm-row blocks",
    samples=_cg_update_samples, nsamples=3,
    shape_case=_cg_update_shape_case,
))

XPBY_DOT = kreg.register(KernelSpec(
    family="cg_fused", name="xpby_dot",
    pallas=xpby_dot_pallas, ref=xpby_dot_ref, fallback="jnp",
    block_args=("bm",), default_block=(32,),
    block_space=((8,), (16,), (32,), (64,), (128,)),
    supports=lambda block, x, y, beta, **kw: rows_divisible(x, block[0]),
    tol=1e-4,
    layout="complex leaves -> re/im (M, Y) f32 row planes, bm-row blocks",
    samples=_xpby_samples, nsamples=2,
    shape_case=_xpby_shape_case,
    properties=(_xpby_nodot_consistency,),
))


def cg_update(alpha, p, ap, x, r, impl="auto", block=None):
    """Fused ``x' = x + alpha*p``, ``r' = r - alpha*Ap`` with the
    ``rs = sum |r'|^2`` epilogue; one pass over the operands.
    Returns ``(x', r', rs)``; ``rs`` is a real f32 scalar (a local
    partial when the operands are shards)."""
    impl, block = CG_UPDATE.resolve(impl, block, alpha, p, ap, x, r)
    if impl != "pallas":
        return cg_update_ref(alpha, p, ap, x, r)
    a = jnp.reshape(jnp.real(alpha).astype(jnp.float32), (1, 1))
    pr, pi, apr, api, xr, xi, rr, ri = [
        *planes(p), *planes(ap), *planes(x), *planes(r)]
    xr2, xi2, rr2, ri2, rs = cg_update_pallas(
        a, pr, pi, apr, api, xr, xi, rr, ri,
        bm=block[0], interpret=not on_tpu())
    x2 = (xr2 + 1j * xi2).reshape(x.shape).astype(x.dtype)
    r2 = (rr2 + 1j * ri2).reshape(r.shape).astype(r.dtype)
    return x2, r2, rs[0, 0]


CG_UPDATE.dispatch = cg_update


def xpby_dot(x, y, beta, impl="auto", with_dot=True, block=None):
    """Fused ``w = x + beta*y`` with the ``d = sum |w|^2`` epilogue (the
    CG search-direction step).  Returns ``(w, d)``; ``with_dot=False``
    skips the epilogue entirely (``d`` is None) — callers that discard
    it must not pay for an un-DCE-able in-kernel reduction."""
    impl, block = XPBY_DOT.resolve(impl, block, x, y, beta)
    if impl != "pallas":
        if not with_dot:
            return x + beta * y, None
        return xpby_dot_ref(x, y, beta)
    b = jnp.reshape(jnp.real(beta).astype(jnp.float32), (1, 1))
    xr, xi = planes(x)
    yr, yi = planes(y)
    if not with_dot:
        wr, wi = xpby_pallas(b, xr, xi, yr, yi,
                             bm=block[0], interpret=not on_tpu())
        return (wr + 1j * wi).reshape(x.shape).astype(x.dtype), None
    wr, wi, d = xpby_dot_pallas(b, xr, xi, yr, yi,
                                bm=block[0], interpret=not on_tpu())
    w = (wr + 1j * wi).reshape(x.shape).astype(x.dtype)
    return w, d[0, 0]


XPBY_DOT.dispatch = xpby_dot

"""jit'd complex-array wrappers with registry dispatch for the coil ops.

All four ops share one tiling contract: a (J, X, Y) coil stack blocked
``bx`` rows of X at a time (declared once in the specs below).  The
``supports`` rules also close a hole the old hand-rolled dispatch had:
``auto`` now falls back to the jnp ref for X that doesn't tile instead
of tripping the kernel's divisibility assert on TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import registry as kreg
from ..registry import KernelSpec, dim_divisible, on_tpu, split
from .kernel import (coil_adjoint_pallas, coil_forward_pallas,
                     coil_lincomb_pallas, coil_scale_mult_pallas,
                     plane_mult_pallas, sms_adjoint_pallas,
                     sms_lincomb_pallas)
from .ref import (coil_adjoint_ref, coil_forward_ref, coil_lincomb_ref,
                  plane_mult_ref, sms_adjoint_ref, sms_lincomb_ref)

_LAYOUT = "(J, X, Y) complex stack -> re/im f32, bx-row blocks of X"
_SMS_LAYOUT = ("(M, J, X, Y) slice x coil stack -> re/im f32, bx-row blocks "
               "of X, every slice of one channel per step")
_SPACE = ((8,), (16,), (32,), (64,), (128,))


def _cplx(key, shape):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


def _forward_samples(i):
    j, x, y = [(4, 32, 32), (6, 64, 128)][i]
    kc, kx = jax.random.split(jax.random.PRNGKey(300 + i))
    coils, img = _cplx(kc, (j, x, y)), _cplx(kx, (x, y))
    return (coils, img), {}, coil_forward_ref(coils, img)


def _forward_shape_case(seed, m, y):
    if m == 0:
        return None                       # an empty coil plane is not a case
    kc, kx = jax.random.split(jax.random.PRNGKey(seed))
    coils, img = _cplx(kc, (3, m, y)), _cplx(kx, (m, y))
    return (coils, img), {}, coil_forward_ref(coils, img)


def _adjoint_samples(i):
    j, x, y = [(4, 32, 32), (6, 64, 128)][i]
    kc, kz, km = jax.random.split(jax.random.PRNGKey(310 + i), 3)
    coils, z = _cplx(kc, (j, x, y)), _cplx(kz, (j, x, y))
    mask = None if i == 0 else \
        (jax.random.uniform(km, (x, y)) > 0.5).astype(jnp.float32)
    return (coils, z), {"mask": mask}, coil_adjoint_ref(coils, z, mask)


def _adjoint_shape_case(seed, m, y):
    if m == 0:
        return None
    kc, kz = jax.random.split(jax.random.PRNGKey(seed))
    coils, z = _cplx(kc, (3, m, y)), _cplx(kz, (3, m, y))
    return (coils, z), {}, coil_adjoint_ref(coils, z, None)


def _lincomb_samples(i):
    j, x, y = [(4, 32, 32), (6, 64, 64)][i]
    ka, kx, kb, ky, ks = jax.random.split(jax.random.PRNGKey(320 + i), 5)
    a, xs = _cplx(ka, (x, y)), _cplx(kx, (j, x, y))
    if i == 0:                            # b=None scale-mult variant
        scale = jax.random.uniform(ks, (x, y), jnp.float32)
        kw = {"scale": scale}
        return (a, xs), kw, coil_lincomb_ref(a, xs, scale=scale)
    b, ys = _cplx(kb, (x, y)), _cplx(ky, (j, x, y))
    scale = jax.random.uniform(ks, (x, y), jnp.float32)
    kw = {"b": b, "y": ys, "scale": scale}
    return (a, xs), kw, coil_lincomb_ref(a, xs, b, ys, scale)


def _plane_samples(i):
    j, x, y = [(4, 32, 32), (8, 64, 64)][i]
    kz, km = jax.random.split(jax.random.PRNGKey(330 + i))
    z = _cplx(kz, (j, x, y))
    m = jax.random.uniform(km, (x, y), jnp.float32)
    return (z, m), {}, plane_mult_ref(z, m)


def _plane_shape_case(seed, m, y):
    if m == 0:
        return None
    kz, km = jax.random.split(jax.random.PRNGKey(seed))
    z = _cplx(kz, (3, m, y))
    mk = jax.random.uniform(km, (m, y), jnp.float32)
    return (z, mk), {}, plane_mult_ref(z, mk)


COIL_FORWARD = kreg.register(KernelSpec(
    family="coil_mult", name="coil_forward",
    pallas=coil_forward_pallas, ref=coil_forward_ref, fallback="jnp",
    block_args=("bx",), default_block=(32,), block_space=_SPACE,
    supports=lambda block, coils, x, **kw:
        coils.ndim == 3 and x.ndim == 2 and
        dim_divisible(coils.shape[1], block[0]) and coils.shape[0] > 0,
    tol=1e-5, layout=_LAYOUT,
    samples=_forward_samples, nsamples=2,
    shape_case=_forward_shape_case,
))

COIL_ADJOINT = kreg.register(KernelSpec(
    family="coil_mult", name="coil_adjoint",
    pallas=coil_adjoint_pallas, ref=coil_adjoint_ref, fallback="jnp",
    block_args=("bx",), default_block=(32,), block_space=_SPACE,
    supports=lambda block, coils, z, mask=None, **kw:
        coils.ndim == 3 and z.ndim == 3 and
        dim_divisible(coils.shape[1], block[0]) and coils.shape[0] > 0,
    tol=1e-4, layout=_LAYOUT,
    samples=_adjoint_samples, nsamples=2,
    shape_case=_adjoint_shape_case,
))

COIL_LINCOMB = kreg.register(KernelSpec(
    family="coil_mult", name="coil_lincomb",
    pallas=coil_lincomb_pallas, ref=coil_lincomb_ref, fallback="jnp",
    block_args=("bx",), default_block=(32,), block_space=_SPACE,
    supports=lambda block, a, x, b=None, y=None, scale=None, **kw:
        x.ndim == 3 and dim_divisible(x.shape[1], block[0]) and
        x.shape[0] > 0,
    tol=1e-5, layout=_LAYOUT,
    samples=_lincomb_samples, nsamples=2,
))

PLANE_MULT = kreg.register(KernelSpec(
    family="coil_mult", name="plane_mult",
    pallas=plane_mult_pallas, ref=plane_mult_ref, fallback="jnp",
    block_args=("bx",), default_block=(32,), block_space=_SPACE,
    supports=lambda block, z, m, **kw:
        z.ndim == m.ndim + 1 and z.ndim == 3 and
        dim_divisible(z.shape[1], block[0]) and z.shape[0] > 0,
    tol=1e-5, layout=_LAYOUT,
    samples=_plane_samples, nsamples=2,
    shape_case=_plane_shape_case,
))


def _mix(seed, m):
    """A random (m, m) complex partition-encoding matrix (host constant):
    the kernels take any mixing, not only the DFT phase cycle."""
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((m, m))
            + 1j * rng.standard_normal((m, m))).astype(np.complex64)


def _sms_lincomb_samples(i):
    m, j, x, y = [(3, 2, 16, 32), (2, 3, 32, 64)][i]
    ka, kx, kb, ky, ks = jax.random.split(jax.random.PRNGKey(340 + i), 5)
    a, xs = _cplx(ka, (m, x, y)), _cplx(kx, (m, j, x, y))
    scale = jax.random.uniform(ks, (x, y), jnp.float32)
    kw = {"scale": scale, "mix": _mix(340 + i, m)}
    if i == 0:                            # b=None: G's one-term form
        return (a, xs), kw, sms_lincomb_ref(a, xs, **kw)
    b, ys = _cplx(kb, (m, x, y)), _cplx(ky, (m, j, x, y))
    kw.update(b=b, y=ys)
    return (a, xs), kw, sms_lincomb_ref(a, xs, **kw)


def _sms_adjoint_samples(i):
    m, j, x, y = [(3, 4, 16, 32), (2, 2, 32, 64)][i]
    kv, kc, ks, kg = jax.random.split(jax.random.PRNGKey(350 + i), 4)
    v, c = _cplx(kv, (m, j, x, y)), _cplx(kc, (m, j, x, y))
    scale = (jax.random.uniform(ks, (x, y)) > 0.3).astype(jnp.float32)
    g = _cplx(kg, (m, x, y))
    kw = {"mix": _mix(350 + i, m)}
    return (v, c, scale, g), kw, sms_adjoint_ref(v, c, scale, g, **kw)


def _sms_adjointness(seed=0):
    """Property: sms_adjoint's unmixing is the adjoint of sms_lincomb's
    mixing, <lincomb(1, x), v> = <x, adjoint(v)[1]> with unit planes."""
    m, j, x, y = 3, 2, 16, 32
    kx, kv = jax.random.split(jax.random.PRNGKey(360 + seed))
    xs, v = _cplx(kx, (m, j, x, y)), _cplx(kv, (m, j, x, y))
    ones = jnp.ones((m, x, y), jnp.complex64)
    mix = _mix(360 + seed, m)
    fwd = sms_lincomb(ones, xs, mix=mix, impl="pallas")
    _, back = sms_adjoint(v, jnp.zeros_like(xs), jnp.ones((x, y)), ones,
                          mix=mix, impl="pallas")
    lhs, rhs = jnp.vdot(fwd, v), jnp.vdot(xs, back)
    assert abs(lhs - rhs) <= 1e-4 * abs(lhs), (lhs, rhs)


def _sms_supports(block, stack):
    return stack.ndim == 4 and dim_divisible(stack.shape[2], block[0]) \
        and stack.shape[1] > 0


SMS_LINCOMB = kreg.register(KernelSpec(
    family="coil_mult", name="sms_lincomb",
    pallas=sms_lincomb_pallas, ref=sms_lincomb_ref, fallback="jnp",
    block_args=("bx",), default_block=(32,), block_space=_SPACE,
    supports=lambda block, a, x, **kw: _sms_supports(block, x),
    tol=1e-5, layout=_SMS_LAYOUT,
    samples=_sms_lincomb_samples, nsamples=2,
))

SMS_ADJOINT = kreg.register(KernelSpec(
    family="coil_mult", name="sms_adjoint",
    pallas=sms_adjoint_pallas, ref=sms_adjoint_ref, fallback="jnp",
    block_args=("bx",), default_block=(32,), block_space=_SPACE,
    supports=lambda block, v, coils, *a, **kw: _sms_supports(block, v),
    tol=1e-4, layout=_SMS_LAYOUT,
    samples=_sms_adjoint_samples, nsamples=2,
    properties=(_sms_adjointness,),
))


def _static_mix(mix) -> tuple:
    """The (Q, M) matrix as the kernels' static nested (re, im) tuples."""
    return tuple(tuple((float(np.real(v)), float(np.imag(v))) for v in row)
                 for row in np.asarray(mix))


def sms_lincomb(a, x, b=None, y=None, scale=None, *, mix, impl="auto",
                block=None):
    """out_qj = scale * Sum_m mix[q, m] (a_m x_mj + b_m y_mj) in one fused
    pass: SMS-NLINV's G/DG pointwise chain with the partition encoding
    ``mix`` (a host (Q, M) constant) folded in."""
    impl, block = SMS_LINCOMB.resolve(impl, block, a, x, b=b, y=y,
                                      scale=scale, mix=mix)
    if impl != "pallas":
        return sms_lincomb_ref(a, x, b, y, scale, mix=mix)
    X, Y = x.shape[-2:]
    s = jnp.ones((X, Y), jnp.float32) if scale is None \
        else jnp.asarray(scale, jnp.float32)
    br = bi = yr = yi = None
    if b is not None:
        (br, bi), (yr, yi) = split(b), split(y)
    zr, zi = sms_lincomb_pallas(*split(a), *split(x), br, bi, yr, yi, s,
                                mix=_static_mix(mix), bx=block[0],
                                interpret=not on_tpu())
    return (zr + 1j * zi).astype(x.dtype)


SMS_LINCOMB.dispatch = sms_lincomb


def sms_adjoint(v, coils, scale, g, *, mix, impl="auto", block=None):
    """With z_mj = scale * Sum_q conj(mix[q, m]) v_qj, returns
    ``(Sum_j conj(coils_mj) z_mj, g_m z_mj)`` in one pass: SMS-NLINV's
    DG^H unmixing fused into the channel sum and the coil-update
    product, so z is never written."""
    impl, block = SMS_ADJOINT.resolve(impl, block, v, coils, scale, g,
                                      mix=mix)
    if impl != "pallas":
        return sms_adjoint_ref(v, coils, scale, g, mix=mix)
    pr, pi, wr, wi = sms_adjoint_pallas(
        *split(v), *split(coils), jnp.asarray(scale, jnp.float32), *split(g),
        mix=_static_mix(mix), bx=block[0], interpret=not on_tpu())
    return ((pr + 1j * pi).astype(v.dtype),
            (wr + 1j * wi).astype(v.dtype))


SMS_ADJOINT.dispatch = sms_adjoint


def coil_forward(coils, x, impl="auto", block=None):
    impl, block = COIL_FORWARD.resolve(impl, block, coils, x)
    if impl != "pallas":
        return coil_forward_ref(coils, x)
    cr, ci = split(coils)
    xr, xi = split(x)
    zr, zi = coil_forward_pallas(cr, ci, xr, xi,
                                 bx=block[0], interpret=not on_tpu())
    return (zr + 1j * zi).astype(coils.dtype)


COIL_FORWARD.dispatch = coil_forward


def coil_lincomb(a, x, b=None, y=None, scale=None, impl="auto", block=None):
    """out_j = scale * (a * x_j + b * y_j) in one fused pass — the
    generalized coil pointwise chain of NLINV's G/DG (``fov*(rho*c)``,
    ``fov*(drho*c0 + rho0*dc)``) without materialized intermediates."""
    impl, block = COIL_LINCOMB.resolve(impl, block, a, x, b=b, y=y,
                                       scale=scale)
    if impl != "pallas":
        return coil_lincomb_ref(a, x, b, y, scale)
    J, X, Y = x.shape
    ar, ai = split(jnp.broadcast_to(a, (X, Y)))
    xr, xi = split(x)
    # scale=None streams a ones plane through the kernel; acceptable
    # because every hot-path caller (G/DG) passes the FOV scale — only
    # b=None is frequent enough to warrant its own kernel variant.
    s = jnp.ones((X, Y), jnp.float32) if scale is None \
        else jnp.asarray(scale, jnp.float32)
    if b is None:
        zr, zi = coil_scale_mult_pallas(ar, ai, xr, xi, s,
                                        bx=block[0], interpret=not on_tpu())
        return (zr + 1j * zi).astype(x.dtype)
    br, bi = split(jnp.broadcast_to(b, (X, Y)))
    yr, yi = split(y)
    zr, zi = coil_lincomb_pallas(ar, ai, xr, xi, br, bi, yr, yi, s,
                                 bx=block[0], interpret=not on_tpu())
    return (zr + 1j * zi).astype(x.dtype)


COIL_LINCOMB.dispatch = coil_lincomb


def plane_mult(z, m, impl="auto", block=None):
    """z_j * m: the mask / FOV / Sobolev-weight broadcast multiply as one
    fused pointwise pass over the coil stack."""
    impl, block = PLANE_MULT.resolve(impl, block, z, m)
    if impl != "pallas":
        return plane_mult_ref(z, jnp.asarray(m, jnp.float32))
    zr, zi = split(z)
    outr, outi = plane_mult_pallas(zr, zi, jnp.asarray(m, jnp.float32),
                                   bx=block[0], interpret=not on_tpu())
    return (outr + 1j * outi).astype(z.dtype)


PLANE_MULT.dispatch = plane_mult


def coil_adjoint(coils, z, mask=None, impl="auto", block=None):
    impl, block = COIL_ADJOINT.resolve(impl, block, coils, z, mask=mask)
    if impl != "pallas":
        return coil_adjoint_ref(coils, z, mask)
    cr, ci = split(coils)
    zr, zi = split(z)
    m = jnp.ones(coils.shape[1:], jnp.float32) if mask is None \
        else jnp.asarray(mask, jnp.float32)
    outr, outi = coil_adjoint_pallas(cr, ci, zr, zi, m,
                                     bx=block[0], interpret=not on_tpu())
    return (outr + 1j * outi).astype(coils.dtype)


COIL_ADJOINT.dispatch = coil_adjoint

"""coil_mult + masked_allreduce kernels' consistency with the NLINV
operators they implement.  (Kernel-vs-oracle parity sweeps moved to the
shared registry harness, ``tests/test_kernel_registry.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.coil_mult import coil_adjoint


def _cplx(key, shape):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, shape) +
            1j * jax.random.normal(k2, shape)).astype(jnp.complex64)


def test_lincomb_implements_dg_pointwise_chain():
    """The fused DG image chain fov*(drho*c0 + rho0*dc) == the unfused
    expression in NlinvOps.DG."""
    from repro.nlinv import phantom
    from repro.nlinv.operators import make_ops, sobolev_weight, uinit
    d = phantom.make_dataset(n=16, ncoils=4, nspokes=5, frames=1)
    ops = make_ops(d["masks"][0], d["fov"], sobolev_weight(d["grid"]))
    g = d["grid"]
    u0 = uinit(4, g)
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    du = {"rho": _cplx(ks[0], (g, g)), "chat": _cplx(ks[1], (4, g, g))}
    want = ops.DG(u0, du)
    got = ops.DG_fused(ops.precompute(u0), du)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_kernels_implement_dgh_channel_sum():
    """The fused adjoint kernel computes exactly the Sum_j conj(c_j) z_j
    + M_Omega step inside NlinvOps.DGH."""
    from repro.nlinv import phantom
    from repro.nlinv.operators import make_ops, sobolev_weight, uinit
    d = phantom.make_dataset(n=16, ncoils=4, nspokes=5, frames=1)
    ops = make_ops(d["masks"][0], d["fov"], sobolev_weight(d["grid"]))
    u0 = uinit(4, d["grid"])
    r = _cplx(jax.random.PRNGKey(3), (4, d["grid"], d["grid"]))
    want = ops.DGH(u0, r)["rho"]
    c0 = ops.coils(u0["chat"])
    from repro.nlinv.operators import ifft2c
    z = ops.fov[None] * ifft2c(ops.mask[None] * r)
    got = coil_adjoint(c0, z, mask=None, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# -- SMS mixing kernels (interpret mode) against their ref.py twins ---------

_SMS = (3, 4, 32, 32)          # slices M, channels J, grid


def _sms_operands(seed):
    from repro.nlinv.operators import sms_phase
    m, j, x, y = _SMS
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    planes = [_cplx(k, (m, x, y)) for k in ks[:2]]
    stacks = [_cplx(k, (m, j, x, y)) for k in ks[2:4]]
    fov = (jax.random.uniform(ks[4], (x, y)) > 0.4).astype(jnp.float32)
    return planes, stacks, fov, sms_phase(m)


@pytest.mark.parametrize("two_term", [False, True], ids=["G", "DG"])
def test_sms_lincomb_kernel_matches_ref(two_term):
    from repro.kernels.coil_mult import sms_lincomb, sms_lincomb_ref
    (a, b), (x, y), fov, xi = _sms_operands(70 + two_term)
    kw = dict(b=b, y=y) if two_term else {}
    got = sms_lincomb(a, x, scale=fov, mix=xi, impl="pallas", **kw)
    want = sms_lincomb_ref(a, x, scale=fov, mix=xi, **kw)
    assert got.shape == (_SMS[0],) + x.shape[1:]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_sms_adjoint_kernel_matches_ref():
    from repro.kernels.coil_mult import sms_adjoint, sms_adjoint_ref
    (g, _), (v, c), fov, xi = _sms_operands(72)
    got = sms_adjoint(v, c, fov, g, mix=xi, impl="pallas")
    want = sms_adjoint_ref(v, c, fov, g, mix=xi)
    for a, b in zip(got, want, strict=True):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_sms_kernels_implement_the_sms_operator():
    """The fused SMS chains equal the plain SmsOps algebra: the forward
    mixing Sum_m Xi_qm fov rho_m c_mj, and the unmixed channel sum."""
    from repro.kernels.coil_mult import sms_adjoint, sms_lincomb
    from repro.nlinv.operators import make_ops, sobolev_weight
    (rho, _), (c, v), fov, xi = _sms_operands(73)
    mask = np.ones((_SMS[0],) + _SMS[2:], np.float32)
    ops = make_ops(mask, fov, sobolev_weight(_SMS[2]))
    want = fov * ops._xi(rho[:, None] * c)
    got = sms_lincomb(rho, c, scale=fov, mix=xi, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    z = fov * ops._xi(v, adjoint=True)
    prod, _ = sms_adjoint(v, c, fov, jnp.ones_like(rho), mix=xi,
                          impl="pallas")
    np.testing.assert_allclose(np.asarray(prod),
                               np.asarray(jnp.sum(jnp.conj(c) * z, axis=1)),
                               rtol=1e-4, atol=1e-4)

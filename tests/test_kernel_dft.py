"""The 2-D DFT kernel (``repro.kernels.dft``) at the cells' size, in
interpret mode, and the library FFT's choice between it and XLA's FFT.

Parity is against numpy in float64; the kernel may err at most twice as
much as ``jnp.fft`` on the same input.  The described-chip compiles are
in ``tests/test_tpu_compile.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry
from repro.kernels.dft import dft2c, dft2c_ref
from repro.kernels.dft import ops as dft_ops
from repro.lib import fft as lfft
from repro.lib.plan import PlanCache, default_cache

N = 768


def _cplx(seed, shape):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _exact(x, inverse, centered):
    x = x.astype(np.complex128)
    if centered:
        x = np.fft.ifftshift(x, axes=(-2, -1))
    x = (np.fft.ifft2(x, norm="ortho") if inverse
         else np.fft.fft2(x, norm="ortho"))
    if centered:
        x = np.fft.fftshift(x, axes=(-2, -1))
    return x


def _rel(got, want):
    return float(np.linalg.norm(np.asarray(got) - want)
                 / np.linalg.norm(want))


@pytest.fixture(scope="module")
def stacks():
    return {b: _cplx(b, (b, N, N)) for b in (8, 2)}


@pytest.mark.parametrize("inverse", [False, True], ids=["forward", "inverse"])
@pytest.mark.parametrize("batch", [8, 2])
def test_parity_with_float64(stacks, batch, inverse):
    """Centred and plain, the kernel's relative L2 error against numpy's
    float64 transform is at most twice XLA's own on the same input."""
    x = stacks[batch]
    for centered in (True, False):
        want = _exact(x, inverse, centered)
        got = dft2c(jnp.asarray(x), inverse, centered, impl="pallas")
        xla = dft2c_ref(jnp.asarray(x), inverse, centered)
        err, floor = _rel(got, want), _rel(xla, want)
        assert err <= 2 * floor, (centered, err, floor)
        assert err < 5e-7, (centered, err)


def test_round_trip_and_adjoint(stacks):
    """inverse(forward(x)) = x, and <F x, y> = <x, F^H y> with F^H the
    inverse transform."""
    x = jnp.asarray(stacks[2])
    y = jnp.asarray(_cplx(7, (2, N, N)))
    fx = dft2c(x, impl="pallas")
    back = dft2c(fx, inverse=True, impl="pallas")
    assert _rel(back, np.asarray(x)) <= 1e-6
    lhs = jnp.vdot(fx, y)
    rhs = jnp.vdot(x, dft2c(y, inverse=True, impl="pallas"))
    assert abs(lhs - rhs) <= 1e-5 * abs(lhs), (lhs, rhs)


@pytest.mark.parametrize("shape,dtype", [
    ((8, 32, 32), jnp.complex64),
    ((2, 100, 100), jnp.complex64),
    ((2, 770, 770), jnp.complex64),
    ((2, 768, 384), jnp.complex64),
    ((2, 768, 768), jnp.complex128),
    ((2, 1280, 1280), jnp.complex64),
], ids=["32", "100", "770", "non-square", "complex128", "r10"])
def test_fallback_shapes_keep_xla(monkeypatch, shape, dtype):
    """On a TPU the plan picks the kernel by shape alone; every other
    geometry keeps XLA's FFT and records ``impl == "xla"``."""
    monkeypatch.setattr(dft_ops, "on_tpu", lambda: True)
    cache = PlanCache()
    before = lfft.impl_builds().get("xla", 0)
    plan = lfft.plan_fft2(shape, dtype, centered=True, cache=cache)
    assert plan.meta["impl"] == "xla"
    assert lfft.impl_builds().get("xla", 0) == before + 1
    # the grid the kernel takes, for contrast
    hit = lfft.plan_fft2((2, N, N), jnp.complex64, cache=cache)
    assert hit.meta["impl"] == "dft_pallas"
    if dtype == jnp.complex64 and shape[-1] <= 100:
        x = jnp.asarray(_cplx(3, shape))
        np.testing.assert_array_equal(plan(x), dft2c_ref(x, centered=True))


def test_off_tpu_every_plan_is_xla():
    cache = PlanCache()
    plan = lfft.plan_fft2((2, N, N), jnp.complex64, cache=cache)
    assert not registry.on_tpu() and plan.meta["impl"] == "xla"


def test_nlinv_frame_with_the_kernel_matches_xla(monkeypatch):
    """One NLINV frame at grid 256 (= 2 x 128) with the kernel forced in
    interpret mode equals the frame on XLA's FFT to 1e-5."""
    from repro.nlinv import phantom
    from repro.nlinv.operators import sobolev_weight, uinit
    from repro.nlinv.recon import Reconstructor

    d = phantom.make_dataset(n=128, ncoils=4, nspokes=9, frames=1, seed=2)
    assert d["grid"] == 256
    u0 = uinit(d["ncoils"], d["grid"])
    args = (jnp.asarray(d["y"][0]), jnp.asarray(d["masks"][0]),
            jnp.asarray(d["fov"]), jnp.asarray(sobolev_weight(d["grid"])),
            u0, u0)

    def frame():
        default_cache().clear()
        rec = Reconstructor(newton=3, cg_iters=4, channel_sum="full")
        return jax.block_until_ready(rec(*args))

    try:
        want_u, want = frame()
        monkeypatch.setattr(lfft, "dft2c",
                            functools.partial(dft2c, impl="pallas"))
        monkeypatch.setattr(lfft, "dft_engages", dft_ops.takes)
        before = lfft.impl_builds().get("dft_pallas", 0)
        registry.reset_tally()
        got_u, got = frame()
        assert lfft.impl_builds().get("dft_pallas", 0) == before + 2
        assert registry.tally()["dft.dft2c"] == {"pallas-interpret": 2}
    finally:
        default_cache().clear()
    assert _rel(got, np.asarray(want)) <= 1e-5
    for k in ("rho", "chat"):
        assert _rel(got_u[k], np.asarray(want_u[k])) <= 1e-5, k

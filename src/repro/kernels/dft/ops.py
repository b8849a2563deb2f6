"""Registry dispatch for the 2-D DFT kernel: complex stacks in and out.

The kernel takes a complex64 stack of square planes whose side is
N = r * 128 with r in ``RADICES``; every other operand, and ``auto``
off a TPU, takes XLA's FFT (the oracle).  ``repro.lib.fft`` dispatches
through here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import registry as kreg
from ..registry import KernelSpec, on_tpu, split
from .kernel import LANES, RADICES, dft2c_pallas, dft_constants
from .ref import dft2c_ref

_LAYOUT = ("(..., N, N) complex64 stack, N = r * 128 -> re/im f32 planes, "
           "one plane per grid step, br rows per matmul chunk")


def takes(x) -> bool:
    """Is ``x`` (an array or a ShapeDtypeStruct) a stack the kernel
    transforms: complex64, square trailing planes of N = r * 128 with
    r in ``RADICES``, at least one plane?"""
    shape = tuple(x.shape)
    if len(shape) < 2 or jnp.dtype(x.dtype) != jnp.complex64:
        return False
    n = shape[-1]
    # N = r * 128 also gives the N % 4 == 0 the folded centring needs
    return (shape[-2] == n and n % LANES == 0 and n // LANES in RADICES
            and all(d > 0 for d in shape[:-2]))


def _cplx(key, shape):
    kr, ki = jax.random.split(key)
    return (jax.random.normal(kr, shape) +
            1j * jax.random.normal(ki, shape)).astype(jnp.complex64)


def _samples(i):
    shape, kw = [((3, 128, 128), {"inverse": False, "centered": True}),
                 ((2, 384, 384), {"inverse": True, "centered": False})][i]
    x = _cplx(jax.random.PRNGKey(400 + i), shape)
    return (x,), kw, dft2c_ref(x, **kw)


def _shape_case(seed, m, y):
    if m == 0:
        return None                       # an empty plane is not a case
    x = _cplx(jax.random.PRNGKey(seed), (2, m, y))
    return (x,), {}, dft2c_ref(x)


def _adjointness(seed=0):
    """<F x, y> = <x, F^H y>, F^H the inverse transform, centred."""
    kx, ky = jax.random.split(jax.random.PRNGKey(410 + seed))
    x, y = _cplx(kx, (2, 256, 256)), _cplx(ky, (2, 256, 256))
    lhs = jnp.vdot(dft2c(x, impl="pallas"), y)
    rhs = jnp.vdot(x, dft2c(y, inverse=True, impl="pallas"))
    assert abs(lhs - rhs) <= 1e-5 * abs(lhs), (lhs, rhs)


def _round_trip(seed=0):
    """inverse(forward(x)) = x."""
    x = _cplx(jax.random.PRNGKey(420 + seed), (2, 384, 384))
    back = dft2c(dft2c(x, impl="pallas"), inverse=True, impl="pallas")
    err = jnp.linalg.norm(back - x) / jnp.linalg.norm(x)
    assert err <= 1e-6, err


DFT2C = kreg.register(KernelSpec(
    family="dft", name="dft2c",
    pallas=dft2c_pallas, ref=dft2c_ref, fallback="xla",
    block_args=("br",), default_block=(256,),
    block_space=((128,), (256,), (384,), (768,)),
    supports=lambda block, x, **kw: takes(x) and block[0] % LANES == 0,
    tol=1e-5, layout=_LAYOUT,
    samples=_samples, nsamples=2,
    shape_case=_shape_case,
    properties=(_adjointness, _round_trip),
))


def engages(x) -> bool:
    """Does ``impl="auto"`` run the kernel on ``x`` (an array or a
    ShapeDtypeStruct)?"""
    return on_tpu() and DFT2C.supports(DFT2C.pick_block(None), x)


def dft2c(x, inverse=False, centered=True, impl="auto", block=None):
    """Ortho-normalised 2-D DFT over the trailing two dims of ``x``
    (``centered``: fftshift . fft2 . ifftshift), as the Pallas kernel
    where it takes ``x`` and XLA's FFT elsewhere."""
    impl, block = DFT2C.resolve(impl, block, x)
    if impl != "pallas":
        return dft2c_ref(x, inverse, centered)
    n = x.shape[-1]
    xr, xi = split(x.reshape((-1, n, n)))
    outr, outi = dft2c_pallas(xr, xi, dft_constants(n, bool(inverse),
                                                    bool(centered)),
                              inverse=bool(inverse), br=block[0],
                              interpret=not on_tpu())
    return lax.complex(outr, outi).reshape(x.shape)


DFT2C.dispatch = dft2c

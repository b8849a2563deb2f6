"""Ahead-of-time compiles of the NLINV main-path kernels for a TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
tiling rules, VMEM limits, SMEM layouts under ``vmap``.  Here each
Pallas entry is lowered with ``interpret=False`` and compiled for one
chip of a *described* ``v5e:2x2`` topology — no accelerator is needed,
only the TPU compiler that ships with jaxlib.  Shapes are the paper's
problem (grid 768, J=8 coils); the batched cases compile each kernel
under ``jax.vmap`` with a leading batch dim, the batching rule the
kernels document.

The topology is described inside a fixture, never at import: several
test workers import this file, and only the one that runs it may load
the TPU library.
"""

import functools
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cg_fused.kernel import (cg_update_pallas,
                                           xpby_dot_pallas, xpby_pallas)
from repro.kernels.coil_mult.kernel import (coil_adjoint_pallas,
                                            coil_forward_pallas,
                                            coil_lincomb_pallas,
                                            coil_scale_mult_pallas,
                                            plane_mult_pallas,
                                            sms_adjoint_pallas,
                                            sms_lincomb_pallas)
from repro.kernels.masked_allreduce.kernel import masked_sum_pallas

J, G = 8, 768           # coil channels, doubled grid (bench fig6 paper size)
M = 3                   # SMS slices (the sms3 benchmark configuration)
BATCH = 4               # clients per batched launch

_STACK = (J, G, G)      # (J, X, Y) coil stack plane
_PLANE = (G, G)         # (X, Y) image plane
_ROWS = (J * G, G)      # chat leaf flattened to (M, Y) row planes
_SCALAR = (1, 1)        # SMEM scalar operand
_SMS_STACK = (M, J, G, G)   # (M, J, X, Y) slice x coil stack
_SMS_PLANE = (M, G, G)      # (M, X, Y) slice planes
# the DFT phase cycle exp(2 pi i q m / M) as the kernels' static tuples
_XI = tuple(tuple((math.cos(2 * math.pi * q * m / M),
                   math.sin(2 * math.pi * q * m / M)) for m in range(M))
            for q in range(M))

# kernel name -> (Pallas entry, block kwarg, operand shapes, all f32)
KERNELS = {
    "cg_update": (cg_update_pallas, "bm", [_SCALAR] + [_ROWS] * 8),
    "xpby": (xpby_pallas, "bm", [_SCALAR] + [_ROWS] * 4),
    "xpby_dot": (xpby_dot_pallas, "bm", [_SCALAR] + [_ROWS] * 4),
    "coil_forward": (coil_forward_pallas, "bx",
                     [_STACK, _STACK, _PLANE, _PLANE]),
    "coil_lincomb": (coil_lincomb_pallas, "bx",
                     [_PLANE, _PLANE, _STACK, _STACK,
                      _PLANE, _PLANE, _STACK, _STACK, _PLANE]),
    "coil_scale_mult": (coil_scale_mult_pallas, "bx",
                        [_PLANE, _PLANE, _STACK, _STACK, _PLANE]),
    "plane_mult": (plane_mult_pallas, "bx", [_STACK, _STACK, _PLANE]),
    "coil_adjoint": (coil_adjoint_pallas, "bx",
                     [_STACK, _STACK, _STACK, _STACK, _PLANE]),
    "masked_sum": (masked_sum_pallas, "bx", [(4, G, G)] * 2 + [_PLANE]),
    "sms_lincomb": (functools.partial(sms_lincomb_pallas, mix=_XI), "bx",
                    [_SMS_PLANE, _SMS_PLANE, _SMS_STACK, _SMS_STACK,
                     _SMS_PLANE, _SMS_PLANE, _SMS_STACK, _SMS_STACK,
                     _PLANE]),
    "sms_lincomb_one": (
        lambda ar, ai, xr, xi, s, **kw: sms_lincomb_pallas(
            ar, ai, xr, xi, None, None, None, None, s, mix=_XI, **kw),
        "bx", [_SMS_PLANE, _SMS_PLANE, _SMS_STACK, _SMS_STACK, _PLANE]),
    "sms_adjoint": (functools.partial(sms_adjoint_pallas, mix=_XI), "bx",
                    [_SMS_STACK] * 4 + [_PLANE, _SMS_PLANE, _SMS_PLANE]),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap4"])
@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name, batched):
    entry, block_arg, shapes = KERNELS[name]
    fn = functools.partial(entry, interpret=False, **{block_arg: 32})
    if batched:
        fn = jax.vmap(fn)
        shapes = [(BATCH,) + s for s in shapes]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the 2-D DFT kernel (repro.kernels.dft) at the cells' grid -----------

def _dft_args(one_chip, lead):
    from repro.kernels.dft.kernel import LANES
    plane = jax.ShapeDtypeStruct(lead + (G, G), jnp.float32,
                                 sharding=one_chip)
    g = jax.ShapeDtypeStruct((G // LANES, 2 * LANES, 2 * LANES),
                             jnp.float32, sharding=one_chip)
    return plane, plane, g


def _dft_alone(xr, xi, g):
    from repro.kernels.dft.kernel import dft2c_pallas
    return dft2c_pallas(xr, xi, g, interpret=False)


def _dft_slices(xr, xi, g):
    """SMS-NLINV's form: the slices one after another under ``lax.map``."""
    return jax.lax.map(lambda p: _dft_alone(*p, g), (xr, xi))


def _dft_in_loop(xr, xi, g):
    """The CG loop's form: the transform inside ``fori_loop``."""
    return jax.lax.fori_loop(0, 2, lambda i, p: _dft_alone(*p, g), (xr, xi))


@pytest.mark.parametrize("form,lead", [
    (_dft_alone, (J,)), (_dft_alone, (2,)),
    (_dft_slices, (M, J)), (_dft_in_loop, (J,))],
    ids=["alone-8", "alone-2", "lax.map-3x8", "fori_loop-8"])
def test_dft_compiles_for_v5e(one_chip, form, lead):
    compiled = jax.jit(form).lower(*_dft_args(one_chip, lead)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_frame_program_at_grid_768_runs_no_xla_fft(topo, monkeypatch):
    """The single-slice frame program at the cells' size, lowered for a
    described chip as a TPU would build it, holds the DFT kernel and no
    XLA FFT: the kernel engages by shape."""
    import importlib
    import pkgutil

    import repro.kernels as kernels
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.env import Communicator
    from repro.core.runtime import DeviceGroup
    from repro.kernels import registry
    from repro.lib import fft as lfft
    from repro.lib.plan import PlanCache, default_cache
    from repro.nlinv.recon import Reconstructor

    registry.specs()
    for m in [registry] + [
            importlib.import_module(f"repro.kernels.{p.name}.ops")
            for p in pkgutil.iter_modules(kernels.__path__) if p.ispkg]:
        if hasattr(m, "on_tpu"):
            monkeypatch.setattr(m, "on_tpu", lambda: True)
    mesh = Mesh(topo.devices[:1], ("data",))
    rec = Reconstructor(Communicator(DeviceGroup.from_mesh(mesh), "data"),
                        newton=7, cg_iters=20)
    rec.plan_cache = PlanCache()
    rep = NamedSharding(mesh, P())
    coil = NamedSharding(mesh, P(None, "data"))
    S = jax.ShapeDtypeStruct
    u = {"rho": S((2, G, G), jnp.complex64, sharding=rep),
         "chat": S((2, J, G, G), jnp.complex64, sharding=coil)}
    args = (S((2, J, G, G), jnp.complex64, sharding=coil),
            S((2, G, G), jnp.float32, sharding=rep),
            S((G, G), jnp.float32, sharding=rep),
            S((G, G), jnp.float32, sharding=rep), u, u)
    default_cache().clear()
    try:
        before = lfft.impl_builds()
        text = rec.fn_batched(2).lower(*args).as_text()
        after = lfft.impl_builds()
    finally:
        default_cache().clear()
    assert "stablehlo.fft" not in text
    assert "tpu_custom_call" in text and "dft2c_pallas" in text
    assert after.get("dft_pallas", 0) - before.get("dft_pallas", 0) == 2
    assert after.get("xla", 0) == before.get("xla", 0)

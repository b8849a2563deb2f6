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

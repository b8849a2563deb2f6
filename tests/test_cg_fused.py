"""ISSUE-5 fused CG hot path.

Kernel-vs-oracle parity sweeps live in the shared registry harness
(``tests/test_kernel_registry.py``, ISSUE 8); this file keeps what the
harness can't express generically:

  * dot-epilogue consistency + <p, Ap> self-adjointness identity
    (normal_pap == the unfused scalar product against normal());
  * fused-vs-unfused CG convergence identity on 1 device (in-process)
    and 4 devices (subprocess, both channel-sum modes);
  * overlapped/chunked ring all-reduce bitwise parity with the plain
    ring, and the fused allreduce_overlap extras/compute contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_with_devices
from repro.kernels.cg_fused import cg_update, xpby_dot


def _cplx(key, shape):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, shape) +
            1j * jax.random.normal(k2, shape)).astype(jnp.complex64)


def test_dot_epilogue_matches_separate_dot():
    """The fused epilogue IS the scalar product: identical (to float
    tolerance) to computing the update then a separate vdot."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    p, ap, x, r = (_cplx(k, (4, 32, 32)) for k in ks)
    for impl in ("jnp", "pallas"):
        _, r2, rs = cg_update(0.25, p, ap, x, r, impl=impl)
        want = float(jnp.real(jnp.vdot(r2, r2)))
        np.testing.assert_allclose(float(rs), want, rtol=1e-4)
        w, d = xpby_dot(r, p, 0.5, impl=impl)
        np.testing.assert_allclose(float(d),
                                   float(jnp.real(jnp.vdot(w, w))),
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# <p, Ap> self-adjointness (the fused curvature scalar)
# ---------------------------------------------------------------------------

def test_normal_pap_matches_unfused_scalar_product():
    """normal_pap's piggybacked <p, Ap> = ||DG p||^2 + alpha ||p||^2 must
    equal the unfused udot(p, normal(p)) — the self-adjointness identity
    the single-collective CG iteration rests on."""
    from repro.nlinv import phantom
    from repro.nlinv.operators import make_ops, sobolev_weight, udot, uinit
    d = phantom.make_dataset(n=16, ncoils=4, nspokes=5, frames=1)
    ops = make_ops(d["masks"][0], d["fov"], sobolev_weight(d["grid"]))
    u0 = uinit(4, d["grid"])
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    p = {"rho": _cplx(ks[0], (d["grid"], d["grid"])),
         "chat": _cplx(ks[1], (4, d["grid"], d["grid"]))}
    alpha = 0.5
    pre = ops.precompute(u0)
    ap_f, pap = ops.normal_pap(
        pre, p, alpha,
        reducer=lambda prod, extras, compute: (prod, extras, compute()))
    ap_u = ops.normal(u0, p, alpha)
    want = float(jnp.real(udot(p, ap_u)))
    np.testing.assert_allclose(float(pap), want, rtol=2e-3)
    for k in ("rho", "chat"):
        np.testing.assert_allclose(np.asarray(ap_f[k]), np.asarray(ap_u[k]),
                                   atol=1e-3, rtol=1e-3)


def test_fused_cg_matches_unfused_single_device():
    """cg_fused == cg on the same normal system (convergence identity)."""
    from repro.nlinv import phantom
    from repro.nlinv.cg import cg, cg_fused
    from repro.nlinv.operators import (make_ops, sobolev_weight, udot,
                                       uinit, uzeros)
    d = phantom.make_dataset(n=16, ncoils=4, nspokes=5, frames=1, seed=2)
    g = d["grid"]
    ops = make_ops(d["masks"][0], d["fov"], sobolev_weight(g))
    u0 = uinit(4, g)
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    rhs = {"rho": _cplx(ks[0], (g, g)), "chat": _cplx(ks[1], (4, g, g))}
    alpha = 0.5
    A = lambda du: ops.normal(u0, du, alpha)
    x_ref = cg(A, rhs, uzeros(4, g), iters=20, tol=1e-8)
    pre = ops.precompute(u0)
    pap = lambda p: ops.normal_pap(
        pre, p, alpha,
        reducer=lambda prod, extras, compute: (prod, extras, compute()))
    x_fused = cg_fused(pap, rhs, iters=20, tol=1e-8)
    scale = float(jnp.max(jnp.abs(x_ref["rho"])))
    err = float(jnp.max(jnp.abs(x_fused["rho"] - x_ref["rho"])))
    assert err < 1e-3 * scale, (err, scale)
    # and both solve the system
    res = jax.tree.map(lambda a, b: a - b, A(x_fused), rhs)
    rel = float(jnp.sqrt(jnp.real(udot(res, res))) /
                jnp.sqrt(jnp.real(udot(rhs, rhs))))
    assert rel < 1e-2, rel


def test_fused_frame_masks_unsampled_kspace():
    """The premasked DGH fast path must not backproject out-of-mask
    garbage in caller-supplied y: fused == unfused even when y carries
    energy at unsampled k-space locations."""
    from repro.nlinv import phantom
    from repro.nlinv.operators import sobolev_weight, uinit
    from repro.nlinv.recon import Reconstructor
    d = phantom.make_dataset(n=16, ncoils=2, nspokes=5, frames=1, seed=9)
    g = d["grid"]
    y = np.asarray(d["y"][0]).copy()
    y += 0.5 * (1.0 - np.asarray(d["masks"][0], np.float32))[None]  # junk
    args = [jnp.asarray(v) for v in
            (y, d["masks"][0], d["fov"], np.asarray(sobolev_weight(g)))]
    outs = {}
    for fused in (False, True):
        rec = Reconstructor(newton=3, cg_iters=5, channel_sum="full",
                            fused=fused)
        u0 = uinit(2, g)
        outs[fused] = rec.fn(*args, u0, u0)[1]
    err = float(jnp.max(jnp.abs(outs[True] - outs[False])))
    scale = float(jnp.max(jnp.abs(outs[False])))
    assert err < 1e-4 * scale, (err, scale)


# ---------------------------------------------------------------------------
# 4-device identities (subprocess)
# ---------------------------------------------------------------------------

FUSED_4DEV = """
from repro.nlinv import phantom
from repro.nlinv.operators import sobolev_weight, uinit
from repro.nlinv.recon import Reconstructor, pad_channels
from repro.core import Environment

d = phantom.make_dataset(n=24, ncoils=6, nspokes=7, frames=1, seed=3)
g = d["grid"]
comm = Environment().subgroup(4)
w = sobolev_weight(g)
yp = pad_channels(np.asarray(d["y"][0]), 4)

for mode in ("full", "crop"):
    outs = {}
    for fused in (False, True):
        rec = Reconstructor(comm, newton=4, cg_iters=8, channel_sum=mode,
                            fused=fused)
        y = rec.put_frame(yp)
        mask = rec.put_const(np.asarray(d["masks"][0]))
        fov = rec.put_const(np.asarray(d["fov"]))
        wd = rec.put_const(np.asarray(w))
        u0 = rec.init_carry(yp.shape[0], g)
        xr = jax.tree.map(lambda a: a + 0, u0)
        outs[fused] = rec.fn(y, mask, fov, wd, u0, xr)[1]
    err = float(jnp.max(jnp.abs(outs[True] - outs[False])))
    scale = float(jnp.max(jnp.abs(outs[False])))
    check(f"fused_matches_unfused_{mode}_4dev", err < 2e-3 * scale)
"""


def test_fused_cg_matches_unfused_4dev():
    run_with_devices(FUSED_4DEV, ndev=4)


OVERLAP_PARITY = """
from functools import partial
from repro.core import Environment
from repro.core.comm import ring_allreduce, all_reduce_overlap
from jax.sharding import PartitionSpec as P

comm = Environment().subgroup(4)
mesh = comm.mesh
x = (np.random.randn(4, 8, 16) + 1j * np.random.randn(4, 8, 16)
     ).astype(np.complex64)

def run(body):
    sm = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P(), check_vma=False)
    return np.asarray(jax.jit(sm)(x))

plain = run(lambda xl: ring_allreduce(xl[0], "data", 4))
chunked = run(lambda xl: ring_allreduce(xl[0], "data", 4, chunks=3))
check("chunked_ring_bitwise", np.array_equal(plain, chunked))

def overlapped(xl):
    red, _, out = all_reduce_overlap(
        xl[0], axis="data", p2p=True, chunks=2,
        compute=lambda: jnp.float32(1.0),
        group=comm.group, mesh_axes=("data",))
    return red + 0 * out
over = run(overlapped)
check("overlap_ring_bitwise", np.array_equal(plain, over))

# the psum schedule with a scalar piggyback agrees with separate psums
# (same collective payload ordering -> identical summation per element)
from jax import lax
def fused_psum(xl):
    red, (s,), _ = all_reduce_overlap(
        xl[0], axis="data", extras=(jnp.real(jnp.vdot(xl[0], xl[0])),),
        group=comm.group, mesh_axes=("data",))
    return red * (s / s)
def sep_psum(xl):
    red = lax.psum(xl[0], "data")
    s = lax.psum(jnp.real(jnp.vdot(xl[0], xl[0])), "data")
    return red * (s / s)
check("piggyback_matches_separate",
      np.allclose(run(fused_psum), run(sep_psum), rtol=1e-5, atol=1e-5))
"""


def test_overlapped_ring_allreduce_bitwise_parity_4dev():
    run_with_devices(OVERLAP_PARITY, ndev=4)


def test_allreduce_overlap_single_program_degenerate():
    from repro.core import Environment
    comm = Environment().subgroup(1)
    x = jnp.arange(16.0).reshape(4, 4)
    red, (s,), out = comm.allreduce_overlap(
        x, ((1, 3), (1, 3)), extras=(jnp.float32(3.0),),
        compute=lambda: jnp.float32(7.0))
    assert float(s) == 3.0 and float(out) == 7.0
    want = np.zeros((4, 4), np.float32)
    want[1:3, 1:3] = np.asarray(x)[1:3, 1:3]
    np.testing.assert_array_equal(np.asarray(red), want)

"""SMS-NLINV (simultaneous multi-slice, Rosenzweig et al. 2018) on the
served path: the slice-coupled operator, its single-slice case, and a
movie through ``StreamScheduler`` + ``NlinvStreamWorkload`` and through
``FrameStream.run`` against the benchmark's plain reference
(``chipbench/reference_sms.py``).  Grid 32, M = 3 slices, J = 4 coils,
seeded data."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Environment
from repro.nlinv.irgnm import irgnm_fused, postprocess
from repro.nlinv.operators import (SmsOps, local_reducer, make_ops,
                                   sms_phase, sobolev_weight, udot, uinit)
from repro.nlinv.recon import Reconstructor
from repro.nlinv.stream import FrameStream
from repro.serve import NlinvStreamWorkload, ServeConfig, StreamScheduler

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import bench, reference, reference_sms  # noqa: E402

M, J, G = 3, 4, 32
# The served SMS frame sits 1e-5 .. 3e-5 from the plain reference at this
# size on the CPU (summation order, up to 140 CG iterations a frame); the
# reference in bfloat16 sits at 9e-3 or more, and a frame solved with the
# wrong slice mixing (Xi = I, or Xi conjugated) at 0.9 or more.
SERVED_TOL = 1e-3
CFG = {"n": G // 2, "coils": J, "slices": M, "newton": 7, "cg_iters": 20,
       "channel_sum": "crop", "assumed": {"spokes": 11, "damping": 0.9}}


def _cplx(rng, shape):
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


def _geometry(seed=0, slices=M):
    rng = np.random.default_rng(seed)
    mask = (rng.random((slices, G, G)) > 0.6).astype(np.float32)
    fov = np.zeros((G, G), np.float32)
    fov[G // 4:3 * G // 4, G // 4:3 * G // 4] = 1.0
    return rng, mask, fov, sobolev_weight(G)


def _point(rng, slices=M):
    return {"rho": jnp.asarray(_cplx(rng, (slices, G, G))),
            "chat": jnp.asarray(0.1 * _cplx(rng, (slices, J, G, G)))}


def test_make_ops_picks_the_sms_operator():
    _, mask, fov, w = _geometry()
    ops = make_ops(mask, fov, w)
    assert isinstance(ops, SmsOps)
    np.testing.assert_allclose(ops.mix @ ops.mix.conj().T, M * np.eye(M),
                               atol=1e-5)
    assert not isinstance(make_ops(mask[0], fov, w), SmsOps)


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_sms_derivative_adjointness(fused):
    """<DG x, r> = <x, DG^H r> for the plain operators and for the fused
    kernels the served program runs."""
    rng, mask, fov, w = _geometry(1)
    ops = make_ops(mask, fov, w)
    u0, x = _point(rng), _point(rng)
    r = ops.sample(jnp.asarray(_cplx(rng, (M, J, G, G))))
    if fused:
        pre = ops.precompute(u0)
        dgx = ops.DG_fused(pre, x)
        dghr, _ = ops.DGH_fused(pre, r, reducer=local_reducer)
    else:
        dgx, dghr = ops.DG(u0, x), ops.DGH(u0, r)
    lhs, rhs = jnp.vdot(dgx, r), udot(x, dghr)
    assert abs(lhs - rhs) <= 1e-5 * abs(lhs), (lhs, rhs)


def test_fused_sms_operators_match_plain():
    rng, mask, fov, w = _geometry(2)
    ops = make_ops(mask, fov, w)
    u0, du = _point(rng), _point(rng)
    r = ops.sample(jnp.asarray(_cplx(rng, (M, J, G, G))))
    pre = ops.precompute(u0)
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
    close(ops.G_fused(u0, c0=pre["c0"]), ops.G(u0))
    close(ops.DG_fused(pre, du), ops.DG(u0, du))
    got, _ = ops.DGH_fused(pre, r, reducer=local_reducer)
    want = ops.DGH(u0, r)
    close(got["rho"], want["rho"])
    close(got["chat"], want["chat"])


def test_slices1_reproduces_todays_frame():
    """``slices=1`` is today's single-slice layout and program: the frame
    of ``Reconstructor(slices=1)`` is the default reconstructor's to
    1e-6 (bitwise, as the same program runs)."""
    rng, mask, fov, w = _geometry(3, slices=1)
    y = _cplx(rng, (J, G, G)) * mask[0]
    outs = []
    for rec in (Reconstructor(newton=3, cg_iters=8),
                Reconstructor(newton=3, cg_iters=8, slices=1)):
        u0 = rec.init_carry(J, G)
        assert u0["rho"].shape == (G, G) and u0["chat"].shape == (J, G, G)
        outs.append(rec(y, mask[0], fov, w, u0, u0)[1])
    assert float(jnp.max(jnp.abs(outs[1] - outs[0]))) <= 1e-6 * float(
        jnp.max(jnp.abs(outs[0])))


def test_one_slice_sms_operator_is_the_single_slice_operator():
    """With M = 1 (Xi = 1, one partition) the SMS operator is the
    single-slice one: every fused application to 1e-6; a whole frame (4
    Newton x 10 CG) to 1e-5, as the CG amplifies float32 summation-order
    differences of the batched FFTs (4.1e-6 measured on the CPU)."""
    rng, mask, fov, w = _geometry(3, slices=1)
    one, sms = make_ops(mask[0], fov, w), make_ops(mask, fov, w)
    assert isinstance(sms, SmsOps) and not isinstance(one, SmsOps)
    lift = lambda t: jax.tree.map(lambda a: a[None], t)
    u, du = (jax.tree.map(lambda a: a[0], _point(rng, 1)) for _ in "ab")
    r = one.sample(jnp.asarray(_cplx(rng, (J, G, G))))
    p1, pm = one.precompute(u), sms.precompute(lift(u))
    rel = lambda got, want: float(jnp.linalg.norm(got[0] - want)
                                  / jnp.linalg.norm(want))
    assert rel(sms.G_fused(lift(u), c0=pm["c0"]),
               one.G_fused(u, c0=p1["c0"])) <= 1e-6
    assert rel(sms.DG_fused(pm, lift(du)), one.DG_fused(p1, du)) <= 1e-6
    got, _ = sms.DGH_fused(pm, r[None], reducer=local_reducer)
    want, _ = one.DGH_fused(p1, r, reducer=local_reducer)
    assert rel(got["rho"], want["rho"]) <= 1e-6
    assert rel(got["chat"], want["chat"]) <= 1e-6

    y = jnp.asarray(_cplx(rng, (J, G, G)) * mask[0])
    u1 = irgnm_fused(one, y, uinit(J, G), newton=4, cg_iters=10)
    um = irgnm_fused(sms, y[None], lift(uinit(J, G)), newton=4, cg_iters=10)
    assert rel(postprocess(sms, um), postprocess(one, u1)) < 1e-5


def test_reconstructor_carries_the_slice_axis():
    rec = Reconstructor(slices=M, newton=2, cg_iters=3)
    u = rec.init_carry(J, G)
    assert u["rho"].shape == (M, G, G) and u["chat"].shape == (M, J, G, G)
    assert rec.coil_dim == 1 and Reconstructor().coil_dim == 0


@pytest.fixture(scope="module")
def movie():
    problem = bench.load_problem(REPO, "sms_nlinv")
    mix = {"scanners": 2, "movie_frames": 3, "noise": 1e-4}
    tr = problem.make_traffic(CFG, mix, 2**31 + 77)
    refs = [problem.reference_movie(CFG, tr, i, 3, None)[0]
            for i in range(2)]
    return tr, refs


def _rec():
    return Reconstructor(Environment().subgroup(1), newton=CFG["newton"],
                         cg_iters=CFG["cg_iters"], channel_sum="crop",
                         slices=M)


def _worst(got, want):
    return max(reference.rel_l2(a, b) for a, b in zip(got, want))


def test_sms_movie_served_through_the_scheduler(movie):
    """Two scanners, bucket 2, three frames each: every image within
    SERVED_TOL of the reference; the bf16 control is not."""
    tr, refs = movie
    wl = NlinvStreamWorkload(_rec(), damping=0.9)
    sched = StreamScheduler(wl, ServeConfig(max_concurrency=2, buckets=(2,)))
    sessions = [sched.open(client=f"s{i}", grid=tr["grid"],
                           ncoils=tr["coils"], slices=M, fov=tr["fov"])
                for i in range(2)]
    for f in range(3):
        for i, s in enumerate(sessions):
            mv = tr["movies"][i]
            assert sched.submit(s, (mv["y"][f], mv["masks"][f]))
        sched.tick()
    assert wl.counters()["slices"] == M
    for i, s in enumerate(sessions):
        imgs = [np.asarray(r) for r in s.results]
        assert len(imgs) == 3 and imgs[0].shape == (M, G, G)
        assert _worst(imgs, refs[i]) < SERVED_TOL
    low, _ = reference_sms.movie(
        tr["movies"][0]["y"], tr["movies"][0]["masks"], tr["fov"],
        newton=CFG["newton"], cg_iters=CFG["cg_iters"], damping=0.9,
        frames=3, lowp=True)
    assert _worst(low, refs[0]) > SERVED_TOL


def test_sms_movie_through_frame_stream(movie):
    tr, refs = movie
    mv = tr["movies"][1]
    imgs, report = FrameStream(_rec(), damping=0.9).run(
        mv["y"], mv["masks"], tr["fov"])
    assert imgs.shape == (3, M, G, G) and report.ncoils == J
    assert _worst(np.asarray(imgs), refs[1]) < SERVED_TOL


def test_workload_pins_the_slices():
    wl = NlinvStreamWorkload(_rec(), damping=0.9)
    sched = StreamScheduler(wl, ServeConfig(max_concurrency=1, buckets=(1,)))
    fov = np.ones((G, G), np.float32)
    with pytest.raises(ValueError, match="slices"):
        sched.open(client="one-slice", grid=G, ncoils=J, fov=fov)
    with pytest.raises(ValueError, match="movie of 3-slice frames"):
        FrameStream(_rec()).run(np.zeros((1, J, G, G), np.complex64),
                                np.zeros((1, G, G)), fov)


def test_reference_phase_cycle_is_the_programs():
    np.testing.assert_allclose(reference_sms.phase_cycle(M), sms_phase(M))

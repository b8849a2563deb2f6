"""Distributed NLINV == single-device NLINV (the paper's §3.2 contract).

4-device mesh, coils split across devices, rho CLONEd; both channel-sum
strategies (paper-faithful full-grid all-reduce and the cropped 2-D
section of kern_all_red_p2p_2d) must agree with the local result.
Also covers channel padding (J=6 on 4 devices).
"""

from helpers import run_with_devices

DIST = """
from repro.nlinv import phantom
from repro.nlinv.irgnm import irgnm, postprocess
from repro.nlinv.operators import make_ops, sobolev_weight, uinit
from repro.nlinv.recon import make_dist_reconstruct, pad_channels
from repro.core import DeviceGroup

d = phantom.make_dataset(n=24, ncoils=6, nspokes=7, frames=1, seed=3)
g = DeviceGroup.all_devices((4,), ("data",))
w = sobolev_weight(d["grid"])

ops = make_ops(d["masks"][0], d["fov"], w)
u_ref = irgnm(ops, jnp.asarray(d["y"][0]), uinit(6, d["grid"]),
              newton=5, cg_iters=20)
img_ref = postprocess(ops, u_ref)

yp = pad_channels(d["y"][0], 4)   # 6 -> 8 channels (zeros)
Jp = yp.shape[0]
for mode in ("full", "crop"):
    fn = make_dist_reconstruct(g, "data", newton=5, cg_iters=20,
                               channel_sum=mode)
    u0 = uinit(Jp, d["grid"])
    u, img = fn(jnp.asarray(yp), jnp.asarray(d["masks"][0]),
                jnp.asarray(d["fov"]), jnp.asarray(w), u0, u0)
    err = float(jnp.max(jnp.abs(img - img_ref)))
    scale = float(jnp.max(jnp.abs(img_ref)))
    check(f"dist_{mode}_matches_local", err < 2e-3 * scale)
"""


def test_distributed_nlinv_4dev():
    run_with_devices(DIST, ndev=4)


SMS_DIST = """
import pathlib
from chipbench import bench, reference
from repro.core import DeviceGroup
from repro.nlinv.recon import Reconstructor
from repro.nlinv.stream import FrameStream

problem = bench.load_problem(pathlib.Path("."), "sms_nlinv")
cfg = {"n": 12, "coils": 6, "slices": 3, "newton": 5, "cg_iters": 12,
       "assumed": {"spokes": 9, "damping": 0.9}}
t = problem.make_traffic(cfg, {"scanners": 1, "movie_frames": 2,
                               "noise": 1e-4}, 11)
mv = t["movies"][0]
ref, _ = problem.reference_movie(cfg, t, 0, 2, None)
g = DeviceGroup.all_devices((4,), ("data",))
# the per-slice channel sum on the wire: 3 slices x the 12 x 12 FOV
# window (+ the CG scalar riding along) with crop, the whole 24 x 24
# grid with full
wire = {"crop": "tensor<433xcomplex<f32>>",
        "full": "tensor<1729xcomplex<f32>>"}
for mode in ("full", "crop"):
    rec = Reconstructor(g, "data", newton=5, cg_iters=12, channel_sum=mode,
                        slices=3)
    imgs, rep = FrameStream(rec).run(mv["y"], mv["masks"], t["fov"])
    check(f"sms_{mode}_pads_6_to_8_coils", rep.ncoils == 8)
    worst = max(reference.rel_l2(a, b) for a, b in zip(np.asarray(imgs), ref))
    # 1.1e-5 measured on the CPU; the controls are in test_nlinv_sms.py
    check(f"sms_{mode}_matches_reference", worst < 1e-3)
    u = rec.init_carry(8, 24)
    txt = rec._build(False).lower(
        jnp.zeros((3, 8, 24, 24), jnp.complex64),
        jnp.zeros((3, 24, 24), jnp.float32), jnp.zeros((24, 24), jnp.float32),
        jnp.zeros((24, 24), jnp.float32), u, u).as_text()
    check(f"sms_{mode}_channel_sum_per_slice", wire[mode] in txt)
"""


def test_distributed_sms_nlinv_4dev():
    """SMS-NLINV with the coils split over 4 devices (6 channels padded
    to 8, 2 per device): the per-slice channel sum is an all-reduce of
    the (M, X, Y) product, windowed to the FOV with ``crop``, and the
    movie matches the plain SMS reference."""
    run_with_devices(SMS_DIST, ndev=4)

"""End-to-end driver (the paper's §3 application): real-time MRI movie
reconstruction with NLINV — acquisition simulation, streaming frames
with temporal regularization through the double-buffered frame engine,
gridding-baseline comparison, per-frame latency/jitter report.

    PYTHONPATH=src python examples/mri_realtime.py --frames 5 --n 48
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/mri_realtime.py --devices 4
"""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Environment
from repro.core.runtime import use_compile_cache
from repro.nlinv import phantom
from repro.nlinv.gridding import gridding_recon
from repro.nlinv.recon import Reconstructor
from repro.nlinv.stream import FrameStream


def nrmse(img, truth, fov):
    m = np.asarray(fov) > 0
    a = np.abs(np.asarray(img))[m]
    b = np.abs(np.asarray(truth))[m]
    a /= max(a.max(), 1e-9)
    b /= max(b.max(), 1e-9)
    return float(np.sqrt(np.mean((a - b) ** 2)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=5)
    ap.add_argument("--n", type=int, default=48, help="matrix size")
    ap.add_argument("--coils", type=int, default=8)
    ap.add_argument("--spokes", type=int, default=11)
    ap.add_argument("--newton", type=int, default=7)
    ap.add_argument("--devices", type=int, default=1,
                    help=">1: channel-split distributed reconstruction")
    ap.add_argument("--channel-sum", default="crop", choices=("full", "crop"))
    ap.add_argument("--report", default="",
                    help="write the latency report JSON here")
    args = ap.parse_args()
    use_compile_cache()

    print(f"acquiring {args.frames} frames (n={args.n}, J={args.coils}, "
          f"{args.spokes} spokes, golden-angle)")
    data = phantom.make_dataset(n=args.n, ncoils=args.coils,
                                nspokes=args.spokes, frames=args.frames)

    ndev = max(args.devices, 1)
    comm = Environment().subgroup(ndev)
    rec = Reconstructor(comm, newton=args.newton, cg_iters=20,
                        channel_sum=args.channel_sum)
    if ndev > 1:
        print(f"distributed: {ndev} devices, coils NATURAL-segmented, "
              f"{args.channel_sum} all-reduce "
              f"(paper kern_all_red_p2p_2d when cropped)")

    engine = FrameStream(rec, damping=0.9)
    movie, report = engine.run(data["y"], data["masks"], data["fov"],
                               report_path=args.report or None)
    jax.block_until_ready(movie)
    s = report.summary()
    print(f"reconstructed {args.frames} frames: first (compile) "
          f"{s['first_frame_ms']:.0f} ms, steady {s['mean_ms']:.1f} ms/frame "
          f"(p95 {s['p95_ms']:.1f}, jitter {s['jitter_ms']:.2f} ms, "
          f"{s['fps']:.1f} fps)")
    pc = s.get("plan_cache", {})
    print(f"plan cache: frame builds {pc.get('frame_builds')}, "
          f"steady builds {pc.get('steady_builds')}, "
          f"hit rate {pc.get('hit_rate')}")
    if args.report:
        print(f"latency report -> {args.report}")
    else:
        print("latency report:", json.dumps(s))

    errs, gerrs = [], []
    for f in range(args.frames):
        errs.append(nrmse(movie[f], data["rho"][f], data["fov"]))
        gr = gridding_recon(jnp.asarray(data["y"][f]),
                            jnp.asarray(data["masks"][f]),
                            jnp.asarray(data["fov"]))
        gerrs.append(nrmse(gr, data["rho"][f], data["fov"]))
    print(f"NRMSE nlinv  : {np.mean(errs):.4f}  (per-frame {np.round(errs,3)})")
    print(f"NRMSE gridding: {np.mean(gerrs):.4f}")
    print("nlinv beats gridding:", np.mean(errs) < np.mean(gerrs))


if __name__ == "__main__":
    main()

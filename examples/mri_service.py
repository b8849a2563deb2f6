"""The multi-stream reconstruction service (ISSUE 7): four synthetic
scanner clients with staggered arrivals streaming through ONE
``StreamScheduler``, every tick one batched SPMD launch over all ready
clients.  Prints the per-client latency/SLO table and the aggregate
throughput.

    PYTHONPATH=src python examples/mri_service.py --frames 6 --n 32
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/mri_service.py --devices 4
"""

import argparse

from repro.core import Environment
from repro.core.runtime import use_compile_cache
from repro.nlinv import phantom
from repro.nlinv.recon import Reconstructor
from repro.serve import NlinvStreamWorkload, ServeConfig, StreamScheduler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--frames", type=int, default=6,
                    help="frames per client")
    ap.add_argument("--n", type=int, default=32, help="matrix size")
    ap.add_argument("--coils", type=int, default=8)
    ap.add_argument("--newton", type=int, default=4)
    ap.add_argument("--cg", type=int, default=10)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--budget-ms", type=float, default=0.0,
                    help="per-frame SLO budget (0 = auto: 2x the first "
                         "steady tick)")
    args = ap.parse_args()
    use_compile_cache()

    K = args.clients
    print(f"service: {K} clients, {args.frames} frames each "
          f"(n={args.n}, J={args.coils}), {max(args.devices, 1)} device(s)")
    datas = [phantom.make_dataset(n=args.n, ncoils=args.coils, nspokes=11,
                                  frames=args.frames, seed=k)
             for k in range(K)]

    comm = Environment().subgroup(max(args.devices, 1))
    rec = Reconstructor(comm, newton=args.newton, cg_iters=args.cg,
                        channel_sum="crop")
    sched = StreamScheduler(
        NlinvStreamWorkload(rec, damping=0.9),
        ServeConfig(max_concurrency=2 * K,
                    budget_ms=args.budget_ms or None,
                    buckets=(1, 2, 4, 8)))

    # staggered arrivals: client k connects at tick k, so the batch
    # width ramps 1 -> 2 -> ... -> K and the scheduler recompiles only
    # at each new bucket width
    sessions = {}
    next_frame = {}
    tick = 0
    while True:
        if tick < K:
            k = tick
            d = datas[k]
            sessions[k] = sched.open(client=f"scanner{k}", grid=d["grid"],
                                     ncoils=args.coils, fov=d["fov"])
            next_frame[k] = 0
            print(f"tick {tick}: scanner{k} connected")
        for k, sess in sessions.items():
            f = next_frame[k]
            if f < args.frames:
                sched.submit(sess, (datas[k]["y"][f], datas[k]["masks"][f]))
                next_frame[k] = f + 1
        if sched.tick() == 0 and all(f >= args.frames
                                     for f in next_frame.values()):
            break
        tick += 1

    if not args.budget_ms and len(sched.tick_ms) > 1:
        # auto-budget for the SLO column: 2x the best steady tick
        budget = 2.0 * min(sched.tick_ms[1:])
        sched.config = ServeConfig(max_concurrency=2 * K,
                                   budget_ms=budget, buckets=(1, 2, 4, 8))
    rep = sched.report()

    print(f"\n{'client':<10} {'frames':>6} {'p50 ms':>8} {'p95 ms':>8} "
          f"{'jitter':>8} {'SLO met':>8}")
    for name, row in sorted(rep["clients"].items()):
        slo = row.get("slo", {})
        met = f"{100 * slo['met']:.0f}%" if slo else "-"
        print(f"{name:<10} {row['frames']:>6} {row['p50_ms']:>8.1f} "
              f"{row['p95_ms']:>8.1f} {row['jitter_ms']:>8.2f} {met:>8}")
    agg = rep["aggregate"]
    budget = sched.config.budget_ms
    print(f"\naggregate: {agg['frames']} frames in {agg['ticks']} ticks, "
          f"{agg['fps']:.1f} fps"
          + (f" (SLO budget {budget:.1f} ms/frame)" if budget else ""))


if __name__ == "__main__":
    main()

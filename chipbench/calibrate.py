"""Read the numbers that a cell's correctness limit is set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6]

In one process (set-up is paid once): for every seed, the timed path's
own entry serves rounds 0..check_frames of the cell's traffic at the
cell's size, and its images are compared with the problem's plain
reference, exactly as a run compares them (the program's reading).  For
every control seed, the problem's reference computed one precision step
down (``lowp=True``; bfloat16 for NLINV) is put in the program's place
and compared the same way (the control's reading).
One JSON line per reading; the benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    bench.keep_every_program()
    import jax

    from chipbench.entries import ENTRIES
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import Environment
    from repro.core.runtime import use_compile_cache
    use_compile_cache()

    cell = bench.load_cell(ROOT, args.workload)
    try:
        devs = bench.require_tpu(jax, cell.chips)
    except bench.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    comm = Environment().subgroup(cell.chips)
    dev0 = list(comm.mesh.devices.flat)[0]
    check = int(cell.mix["check_frames"])
    cfg, problem = cell.cfg, cell.problem
    print(json.dumps({"device": devs[0].device_kind, "chips": cell.chips,
                      "cell": cell.name}), flush=True)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        traffic = problem.make_traffic(cfg, cell.mix, seed)
        entry = ENTRIES[cell.mix["entry"]](cfg, cell.mix, traffic, comm,
                                           problem)
        kept = collections.defaultdict(dict)
        for f in range(check + 1):
            for s in entry.round(f):
                kept[s.scanner][s.frame] = s.image
        entry.close()
        gap, its = bench.compare(cell, traffic, kept, dev0)
        print(json.dumps({"seed": seed, "program": gap, "cg_iters_min": its,
                          "t": time.perf_counter() - T_START}), flush=True)
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        traffic = problem.make_traffic(cfg, cell.mix, seed)
        kept = {}
        for i in range(len(traffic["movies"])):
            low, _ = problem.reference_movie(cfg, traffic, i, check + 1, dev0,
                                             lowp=True)
            kept[i] = dict(enumerate(low))
        gap, _ = bench.compare(cell, traffic, kept, dev0)
        print(json.dumps({"seed": seed, "control_bf16": gap,
                          "t": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

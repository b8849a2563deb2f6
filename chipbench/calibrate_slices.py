"""Read a multi-slice cell's correctness gaps slice by slice.

    python3 chipbench/calibrate_slices.py --workload <cell> --seeds 1,2 \
        [--control-seeds 3,4]

As ``chipbench/calibrate.py`` does, in one process: for every seed the
cell's own entry serves rounds 0..check_frames and its images are
compared with the problem's plain reference (the program's reading); for
every control seed the reference one precision step down (``lowp=True``)
takes the program's place (the control's reading).  Each line gives the
whole gap, as a run computes it, beside the worst gap of each slice (the
problem's ``slice_gaps``).  The benchmark's own runs never run this.
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


def _refs(cell, traffic, device) -> dict:
    check = int(cell.mix["check_frames"])
    return {i: dict(enumerate(cell.problem.reference_movie(
        cell.cfg, traffic, i, check + 1, device)[0]))
        for i in range(len(traffic["movies"]))}


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
        bench.require_tpu(jax, cell.chips)
    except bench.BenchError as e:
        print(f"calibrate_slices: {e}", file=sys.stderr)
        return 2
    comm = Environment().subgroup(cell.chips)
    dev0 = list(comm.mesh.devices.flat)[0]
    check = int(cell.mix["check_frames"])
    problem = cell.problem
    runs = [("program", int(s)) for s in args.seeds.split(",") if s] + [
        ("control_bf16", int(s)) for s in args.control_seeds.split(",") if s]
    for kind, seed in runs:
        traffic = problem.make_traffic(cell.cfg, cell.mix, seed)
        kept = collections.defaultdict(dict)
        if kind == "program":
            entry = ENTRIES[cell.mix["entry"]](cell.cfg, cell.mix, traffic,
                                               comm, problem)
            for f in range(check + 1):
                for s in entry.round(f):
                    kept[s.scanner][s.frame] = s.image
            entry.close()
        else:
            for i in range(len(traffic["movies"])):
                low, _ = problem.reference_movie(cell.cfg, traffic, i,
                                                 check + 1, dev0, lowp=True)
                kept[i] = dict(enumerate(low))
        gap, _ = bench.compare(cell, traffic, kept, dev0)
        slices = problem.slice_gaps(kept, _refs(cell, traffic, dev0))
        print(json.dumps({"seed": seed, kind: gap, "slices": slices,
                          "worst_slice": max(slices),
                          "t": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

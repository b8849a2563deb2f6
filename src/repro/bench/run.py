"""Benchmark sweep driver.

  PYTHONPATH=src python -m repro.bench.run [--size tiny|paper]
      [--devices 1,4] [--only fig4,stream,...] [--out BENCH_paper.json]
      [--sweep SIZE:FIG,FIG ...] [--iters N] [--warmup N] [--list]

``--sweep SIZE:FIGURES`` (repeatable) runs several (size, figure-set)
combinations in ONE artifact — e.g. ``--sweep tiny:fig4,fig5 --sweep
paper:fig5`` gives the cheap tiny coverage everywhere plus paper-size
columns for the transfer figures.  When present it replaces
``--size``/``--only``.

The parent process never initialises a JAX backend (an accelerator
belongs to one process at a time, and the CPU device count is locked at
first init): it spawns one child per requested device count, collects
the children's partial results, computes per-scenario speed-ups vs the
1-device runs, and writes one schema-versioned artifact
(``repro.bench.artifact``).  ``--out -`` prints the table only.  A
child asked for N devices sets the CPU backend's device count to N
(simulated devices, as in ``tests/helpers.py``); on an accelerator host
with more chips it runs on the first N.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import traceback

REPO = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_OUT_DIR = REPO / "benchmarks" / "out"
# lm (per-architecture LM steps) is opt-in: it is paper-size only and far
# heavier than the paper-figure scenarios the CI trajectory tracks.
DEFAULT_FIGURES = ("fig4", "fig5", "fig6", "fig89", "gridding", "serve",
                   "stream", "table1")


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="repro.bench.run",
        description="run registered benchmark scenarios, emit an artifact")
    ap.add_argument("--size", choices=("tiny", "paper"), default="tiny")
    ap.add_argument("--quick", action="store_true",
                    help="alias for --size tiny (old benchmarks.run flag)")
    ap.add_argument("--devices", default="1,4",
                    help="comma-separated device counts (default 1,4)")
    ap.add_argument("--only", default=",".join(DEFAULT_FIGURES),
                    help="comma-separated figure names; 'all' = every "
                         "registered figure (default: paper figures, no lm)")
    ap.add_argument("--out", default="-",
                    help="artifact path (CI uses the BENCH_paper.json "
                         "baseline at the repo root); '-' = print only "
                         "(default — a partial sweep must never clobber "
                         "the committed baseline by accident)")
    ap.add_argument("--out-dir", default=str(DEFAULT_OUT_DIR),
                    help="directory for side artifacts (latency reports)")
    ap.add_argument("--sweep", action="append", default=[],
                    metavar="SIZE:FIGURES",
                    help="repeatable SIZE:FIG,FIG spec; when given, "
                         "replaces --size/--only and every spec runs at "
                         "every --devices count into one artifact")
    ap.add_argument("--iters", type=int, default=None,
                    help="steady-state samples per scenario (default by size)")
    ap.add_argument("--warmup", type=int, default=None,
                    help="warmup calls incl. the compile call (default by size)")
    ap.add_argument("--list", action="store_true",
                    help="list registered scenarios and exit")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--emit", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.quick:
        args.size = "tiny"
    return args


def _figures(args):
    if args.only.strip().lower() == "all":
        return None
    return tuple(f.strip() for f in args.only.split(",") if f.strip())


def _jobs(args) -> list[tuple[str, str]]:
    """The (size, only) combinations this sweep runs — one child per
    (job, device count).  Default: the single --size/--only pair."""
    if not args.sweep:
        return [(args.size, args.only)]
    jobs = []
    for spec in args.sweep:
        size, sep, figs = spec.partition(":")
        size = size.strip()
        if not sep or size not in ("tiny", "paper") or not figs.strip():
            raise SystemExit(f"repro.bench: bad --sweep spec {spec!r} "
                             "(want SIZE:FIG,FIG with SIZE tiny|paper)")
        jobs.append((size, figs.strip()))
    return jobs


def _sampling(args):
    from .harness import SIZE_DEFAULTS
    s = dict(SIZE_DEFAULTS[args.size])
    if args.iters is not None:
        s["iters"] = args.iters
    if args.warmup is not None:
        s["warmup"] = args.warmup
    return s


# ---------------------------------------------------------------------------
# child: one device count, real measurements
# ---------------------------------------------------------------------------

def _child_main(args) -> int:
    import jax

    want = int(args.devices)
    # sizes the CPU backend only; an accelerator's chips are unaffected
    jax.config.update("jax_num_cpu_devices", want)

    from repro.core import Environment
    from repro.core.runtime import use_compile_cache

    from .harness import BenchContext
    from .registry import scenarios

    use_compile_cache()
    got = jax.device_count()
    if got < want:
        print(f"repro.bench: need {want} devices, jax sees {got} "
              f"{jax.devices()[0].platform} device(s)", file=sys.stderr)
        return 2

    out_dir = pathlib.Path(args.out_dir)
    sampling = _sampling(args)
    ctx = BenchContext(size=args.size, devices=want,
                       comm=Environment().subgroup(want),
                       out_dir=out_dir, **sampling)

    runs, failures = [], []
    for key, sc in scenarios(figures=_figures(args)).items():
        if args.size not in sc.sizes or want not in sc.devices:
            continue
        print(f"  [{want}d/{args.size}] {key} ...", file=sys.stderr, flush=True)
        try:
            res = dict(sc.fn(ctx))
        except Exception:
            # one broken scenario must not void the rest of the sweep;
            # the parent fails the run but still reports what measured.
            traceback.print_exc()
            failures.append(f"{key}@d{want}@{args.size}")
            continue
        runs.append({"scenario": key, "figure": sc.figure,
                     "devices": want, "size": args.size, **res})

    from .harness import calibrate
    payload = {
        "host": {"platform": jax.devices()[0].platform,
                 "device_kind": jax.devices()[0].device_kind,
                 "device_count": want, "jax": jax.__version__,
                 "python": sys.version.split()[0]},
        "calibration_ms": calibrate(),
        "runs": runs,
        "failures": failures,
    }
    emit = pathlib.Path(args.emit) if args.emit else None
    if emit is None:
        json.dump(payload, sys.stdout)
    else:
        emit.write_text(json.dumps(payload))
    return 0


# ---------------------------------------------------------------------------
# parent: sweep device counts in subprocesses, merge, write artifact
# ---------------------------------------------------------------------------

def _spawn(args, ndev: int, size: str, only: str,
           emit: pathlib.Path) -> bool:
    cmd = [sys.executable, "-m", "repro.bench.run", "--child",
           "--devices", str(ndev), "--size", size,
           "--only", only, "--out-dir", args.out_dir,
           "--emit", str(emit)]
    if args.iters is not None:
        cmd += ["--iters", str(args.iters)]
    if args.warmup is not None:
        cmd += ["--warmup", str(args.warmup)]
    env = os.environ.copy()
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run(cmd, env=env, cwd=str(REPO))
    if r.returncode != 0:
        print(f"repro.bench: {ndev}-device child failed "
              f"(exit {r.returncode})", file=sys.stderr)
        return False
    return True


def _format_table(art: dict) -> str:
    head = f"{'scenario':<38} {'dev':>3} {'size':>5} {'compile_ms':>11} " \
           f"{'steady_ms':>10} {'speedup':>8}"
    lines = [head, "-" * len(head)]
    for key in sorted(art["scenarios"]):
        r = art["scenarios"][key]
        sp = r.get("speedup_vs_1dev")
        lines.append(
            f"{r['scenario']:<38} {r['devices']:>3} {r['size']:>5} "
            f"{r['compile_ms']:>11.3f} {r['steady_ms']:>10.3f} "
            f"{sp if sp is not None else '-':>8}")
    return "\n".join(lines)


def main(argv=None) -> int:
    args = _parse_args(argv)

    if args.list:
        from .registry import scenarios
        for key, sc in scenarios(figures=_figures(args)).items():
            print(f"{key:<30} sizes={','.join(sc.sizes)} "
                  f"devices={','.join(map(str, sc.devices))}  {sc.doc}")
        return 0

    if args.child:
        return _child_main(args)

    from .artifact import make_artifact, write_artifact
    from .registry import figure_names

    jobs = _jobs(args)
    for _, only in jobs:
        if only.strip().lower() == "all":
            continue
        figs = tuple(f.strip() for f in only.split(",") if f.strip())
        unknown = set(figs) - set(figure_names())
        if unknown:
            raise SystemExit(f"repro.bench: unknown figure(s) "
                             f"{sorted(unknown)}; registered: "
                             f"{list(figure_names())}")

    counts = [int(d) for d in args.devices.split(",") if d.strip()]
    if not counts:
        raise SystemExit("repro.bench: --devices must name at least one count")
    partials, failures = [], []
    for ndev in counts:
        for size, only in jobs:
            with tempfile.NamedTemporaryFile(suffix=".json",
                                             delete=False) as f:
                emit = pathlib.Path(f.name)
            try:
                # a failed child must not void the others' results
                if _spawn(args, ndev, size, only, emit):
                    p = json.loads(emit.read_text())
                    partials.append(p)
                    failures += p.get("failures", [])
                else:
                    failures.append(f"<{ndev}-device {size} child>")
            finally:
                emit.unlink(missing_ok=True)

    runs = [r for p in partials for r in p["runs"]]
    if not runs:
        raise SystemExit("repro.bench: the sweep produced no runs "
                         "(every scenario failed or none matched the "
                         f"requested sizes / --devices {args.devices})")
    sizes = list(dict.fromkeys(size for size, _ in jobs))
    host = dict(partials[0]["host"], size=",".join(sizes),
                device_counts=counts)
    # best (fastest) reference across children = the machine's speed
    # with the least neighbor interference during this sweep
    cal = min(p["calibration_ms"] for p in partials)
    art = make_artifact(runs, host=host, calibration_ms=cal)
    print(_format_table(art))
    if failures:
        # never persist a partial sweep: a baseline missing the failed
        # rows would silently drop them from the regression gate
        print(f"FAILED scenarios: {failures}", file=sys.stderr)
        if args.out != "-":
            print(f"not writing {args.out} (incomplete sweep)",
                  file=sys.stderr)
        return 1
    if args.out != "-":
        path = write_artifact(args.out, art)
        print(f"wrote {path} ({len(runs)} runs)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

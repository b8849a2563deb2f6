"""The harness: one run of one cell, driven by the files it names.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration
(its ``file``), a traffic mix (``chipbench/traffic/<mix>.json``) and the
chips it needs; the configuration names its problem
(``chipbench/problems/<problem>.py``: traffic generator, plain reference,
work count and the program calls the entries drive); its correctness
limits are ``chipbench/limits/<cell>.json`` and each metric is read by
``chipbench/metrics/<metric>.py``.  A run:

  1. finds the cell's files, then a TPU with enough chips (or fails);
  2. makes the traffic from the seed, builds the user entry and serves
     one warm-up round (frame 0 of every scanner): that compiles every
     program the window runs, and is set-up;
  3. measures for ``seconds``: rounds of one frame per scanner, closed
     loop, until the time is up (the last round runs to its end, and the
     window ends with it); a traced run profiles the mix's
     ``trace_rounds`` rounds that follow the window's first round;
  4. reads the peak device memory, frees the program, and compares the
     served images of the first rounds with the problem's plain
     reference;
  5. reads the metrics of the cell (end-to-end ones, or with ``trace``
     the per-layer ones from the profiler trace of the window) and
     returns the result line.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def keep_every_program() -> None:
    """Lift the persistent compilation cache's size cap (one frame
    program's entry is over 100 MiB, and a cap of 192 MiB evicts one
    program while writing the next) and cache programs however fast they
    compiled, so that only the first run of a cell compiles.  Where the
    cache lives is left to ``use_compile_cache()``.  Call before JAX is
    imported."""
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


class BenchError(RuntimeError):
    """A run that cannot give a result (no chip, a missing file)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    problem: object         # the module chipbench/problems/<problem>.py
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _listed(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """Everything a run of cell ``name`` reads, found by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in bench["configs"] if c["name"] == cell["config"]),
                None)
    if conf is None:
        raise BenchError(f"no config {cell['config']!r} in BENCHMARK.json")
    cfg = json.loads((root / conf["file"]).read_text())
    if "problem" not in cfg:
        raise BenchError(f"config {conf['file']} names no problem")
    return Cell(
        name=name, chips=int(cell["chips"]), cfg=cfg,
        problem=load_problem(root, cfg["problem"]),
        mix=json.loads((root / "chipbench" / "traffic"
                        / f"{cell['traffic']}.json").read_text()),
        limits=json.loads((root / "chipbench" / "limits"
                           / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _listed(m, name)],
        per_layer=[m for m in bench["per_layer"] if _listed(m, name)])


def _load_module(path: pathlib.Path, kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: pathlib.Path, metric: str):
    """The ``read(ctx)`` function of ``chipbench/metrics/<metric>.py``."""
    path = root / "chipbench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {metric!r} ({path})")
    return _load_module(path, "metric", metric).read


def load_problem(root: pathlib.Path, problem: str):
    """The module ``chipbench/problems/<problem>.py`` (its interface is in
    ``chipbench/problems/nlinv.py``'s docstring)."""
    path = root / "chipbench" / "problems" / f"{problem}.py"
    if not path.is_file():
        raise BenchError(f"no problem {problem!r} ({path})")
    return _load_module(path, "problem", problem)


def load_peak(root: pathlib.Path, kind: str) -> dict:
    peaks = json.loads((root / "chipbench" / "peaks.json").read_text())
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return peaks[kind]


def require_tpu(jax, chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs


class CompileClock:
    """Backend compiles (count and seconds; a persistent-cache hit counts
    its load) and persistent-cache hits and writes, from JAX's own
    monitoring events."""

    def __init__(self, jax):
        self.compiles = 0
        self.seconds = collections.Counter()
        self.hits = self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds[kw.get("fun_name", "?")] += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""
    cell: Cell
    chips: int
    peak: dict
    setup_s: float
    window_s: float
    served: list            # the window's frames delivered and finite
    step_ms: list           # the program's own step timer, window only
    cg_iters: int           # least work count (NLINV: CG iterations) of
                            # a compared frame, by the reference
    trace: object = None    # trace.Trace of the window (traced runs)

    @property
    def problem(self):
        return self.cell.problem

    @property
    def latencies(self) -> list:
        return [s.latency_ms for s in self.served]

    @property
    def frames(self) -> int:
        return len(self.latencies)


def log(msg: str):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def _cache_entries(path) -> list:
    if not path or not os.path.isdir(path):
        return []
    return sorted((os.path.getsize(os.path.join(path, f))
                   for f in os.listdir(path)), reverse=True)


def serve_window(entry, seconds: float, first_round: int, trace_dir=None,
                 trace_rounds: int = 0, round_ms=None):
    """Rounds until ``seconds`` have passed.  With ``trace_dir``, the
    ``trace_rounds`` rounds after the window's first round run under the
    profiler (a fixed count, so that every traced run reads the same
    work; a trace of four chips holds about a million operations a
    second).  Returns (served, window_s, rounds, traced), ``traced`` being
    the (first round, rounds, seconds) of the profiled part, or None; the
    wall ms of each round are appended to ``round_ms`` if it is given."""
    import jax
    served, f, traced = [], first_round, None
    round_ms = [] if round_ms is None else round_ms

    def one_round():
        nonlocal f
        t = time.perf_counter()
        served.extend(entry.round(f))
        round_ms.append((time.perf_counter() - t) * 1e3)
        f += 1

    t0 = time.perf_counter()
    if trace_dir is not None:
        one_round()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.window"):
                for _ in range(trace_rounds):
                    one_round()
            traced = (f - trace_rounds, trace_rounds, time.perf_counter() - t1)
        finally:
            jax.profiler.stop_trace()
    while f == first_round or time.perf_counter() - t0 < seconds:
        one_round()
    return served, time.perf_counter() - t0, f - first_round, traced


def compare(cell: Cell, traffic: dict, kept: dict, device):
    """Worst relative L2 gap of every scanner's served images of rounds
    0..check_frames against the problem's plain reference chain of the
    same scanner (a missing or failed image counts as an infinite gap),
    and the least work count (NLINV: CG iterations) the reference gave
    one of those frames."""
    from .reference import rel_l2
    check = int(cell.mix["check_frames"])
    gaps, iters = [], []
    for i in range(len(traffic["movies"])):
        frames = kept.get(i, {})
        ref, its = cell.problem.reference_movie(cell.cfg, traffic, i,
                                                check + 1, device)
        gaps += [rel_l2(frames[f], ref[f])
                 if frames.get(f) is not None else float("inf")
                 for f in range(check + 1)]
        iters += its
    # a NaN gap (a non-finite image) counts as the worst
    return (max((g if g == g else float("inf") for g in gaps), default=0.0),
            min(iters))


def run(root: pathlib.Path, workload: str, seed: int, seconds: float,
        traced: bool, *, t_start: float, check_device: bool = True) -> dict:
    import jax
    import numpy as np

    from . import stats
    from . import trace as tracemod
    from .entries import ENTRIES

    cell = load_cell(root, workload)
    readers = {m["name"]: load_reader(root, m["name"])
               for m in (cell.per_layer if traced else cell.end_to_end)}
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no program under {src}: run from a checkout of "
                         f"the repository")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro.core import Environment
    from repro.core.runtime import use_compile_cache
    from repro.kernels import registry

    cache_dir = use_compile_cache()
    devs = require_tpu(jax, cell.chips) if check_device else jax.devices()
    peak = load_peak(root, devs[0].device_kind) if check_device else {
        "flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    log(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}; jax {jax.__version__}; compile cache "
        f"{cache_dir}")
    clock = CompileClock(jax)
    comm = Environment().subgroup(cell.chips)
    cell_devs = list(comm.mesh.devices.flat)

    t = time.perf_counter()
    traffic = cell.problem.make_traffic(cell.cfg, cell.mix, seed)
    log(f"traffic: {len(traffic['movies'])} scanner(s) x "
        f"{cell.mix['movie_frames']} distinct frames of "
        f"{cell.problem.describe(traffic)} made in "
        f"{time.perf_counter() - t:.3f} s")
    entry = ENTRIES[cell.mix["entry"]](cell.cfg, cell.mix, traffic, comm,
                                       cell.problem)
    check = int(cell.mix["check_frames"])
    kept = collections.defaultdict(dict)

    def keep(outcomes):
        for s in outcomes:
            if s.frame <= check:
                kept[s.scanner][s.frame] = s.image

    keep(entry.round(0))                  # warm-up: compiles, is set-up
    warm_steps = len(entry.step_ms())
    setup_s = time.perf_counter() - t_start
    compiles0 = clock.compiles
    log(f"set-up {setup_s:.3f} s: {clock.compiles} compiles "
        f"({sum(clock.seconds.values()):.3f} s), persistent-cache hits "
        f"{clock.hits}, writes {clock.writes}; cache entries (bytes) "
        f"{_cache_entries(cache_dir)[:6]}")
    log(f"kernel impl tally: {json.dumps(registry.tally(), sort_keys=True)}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced \
        else None
    round_ms = []
    served, window_s, rounds, part = serve_window(
        entry, seconds, 1, trace_dir,
        int(cell.mix["trace_rounds"]) if traced else 0, round_ms)
    keep(served)
    step_ms = entry.step_ms()[warm_steps:]
    in_window = clock.compiles - compiles0
    degraded = entry.degraded()
    log(f"window {window_s:.3f} s, {rounds} rounds, compiles inside "
        f"{in_window}")
    log(f"round ms: {json.dumps([round(x, 1) for x in round_ms])}")
    log(f"step ms: {json.dumps([round(x, 1) for x in step_ms])}")
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in cell_devs]
    log(f"peak bytes in use per chip: {mem}")
    entry.close()
    del entry
    gc.collect()

    bad = [s for s in served
           if s.image is not None and not np.isfinite(s.image).all()]
    nonfinite = len(bad)
    failed = sum(1 for s in served if s.failure is not None) + nonfinite
    good = [s for s in served if s.failure is None and s not in bad]
    for s in served:
        s.image = None                    # the compared ones are in kept
    t = time.perf_counter()
    gap, cg_iters = compare(cell, traffic, kept, cell_devs[0])
    log(f"reference over {sum(len(v) for v in kept.values())} images in "
        f"{time.perf_counter() - t:.3f} s; least work count of a frame "
        f"{cg_iters}")
    # every frame handed over is served, finite, at the configured
    # accuracy: none shed, rejected or non-finite, and no step down the
    # deadline ladder (which dials Newton and CG steps down)
    checks = {"image_rel_l2": {"value": gap,
                               "limit": float(cell.limits["image_rel_l2"])},
              "nonfinite_frames": {"value": nonfinite, "limit": 0},
              "failed_frames": {"value": failed, "limit": 0},
              "degraded_steps": {"value": degraded, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    if part is not None:
        # per-layer metrics read the profiled rounds of the window
        r0, r, window_s = part
        good = [s for s in good if r0 <= s.frame < r0 + r]
        step_ms = step_ms[r0 - 1:r0 - 1 + r]
        log(f"traced rounds {r0}..{r0 + r - 1}, {window_s:.3f} s")
    ctx = Context(cell=cell, chips=cell.chips, peak=peak, setup_s=setup_s,
                  window_s=window_s, served=good, step_ms=step_ms,
                  cg_iters=cg_iters)
    breakdown = None
    if traced:
        try:
            ctx.trace = tracemod.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        breakdown = stats.breakdown(ctx.trace)
        by_code = stats.op_seconds(ctx.trace, lambda e: e.code)
        log(f"device seconds by opcode: "
            f"{json.dumps(by_code.most_common(16))}")
        log(f"collectives per frame: "
            f"{json.dumps(stats.collectives_per_frame(ctx))}")
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = readers[m["name"]](ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max((b for b in mem if b is not None),
                                       default=None)}
    if traced:
        device["busy_s"] = stats.busy_s(ctx.trace)
        device["window_s"] = stats.traced_window_s(ctx.trace)
    result = {"correct": bool(correct), "attempted": len(served),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compiles_in_window"] = in_window
    result["checks"] = checks
    return result

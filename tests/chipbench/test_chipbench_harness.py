"""The harness on the CPU at a tiny size: sound runs come out correct,
broken ones do not, cells and metrics are found by their files, and the
command refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from conftest import REPO, TINY_LIMIT, write_root

from chipbench import bench, reference


def _run(root, cell, seed=2**31 + 11, seconds=0.2, traced=False):
    return bench.run(root, cell, seed, seconds, traced,
                     t_start=time.perf_counter(), check_device=False)


@pytest.mark.parametrize("cell", ["tiny.service", "tiny.stream"])
def test_sound_run_is_correct(tiny_root, cell):
    r = _run(tiny_root, cell)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"frames_per_s", "frame_latency_p50_ms",
                                 "frame_latency_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert r["checks"]["image_rel_l2"]["limit"] == TINY_LIMIT


@pytest.mark.parametrize("seconds", [0.01, 0.5])
def test_traced_run_reads_per_layer_metrics(tiny_root, seconds):
    """The mix's trace_rounds rounds after the window's first are traced,
    however short the window."""
    r = bench.run(tiny_root, "tiny.service", 2**31 + 13, seconds, True,
                  t_start=time.perf_counter(), check_device=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 * (1 + 2)
    got = r["metrics"]
    assert {"step_ms_p50", "frame_roofline", "device_idle_pct"} <= set(got)
    # no collective runs on one device: the reader finds nothing to read
    assert "collective_exposed_ms" not in got
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert 0 <= got["device_idle_pct"]["value"] < 100
    assert 0 < got["frame_roofline"]["value"]
    ops = r["breakdown"]["device_ops"]
    assert ops and all(isinstance(n, str) and v > 0 for n, v in ops)
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_serve_window_traces_a_fixed_count_of_rounds():
    class Entry:
        def __init__(self):
            self.rounds = []

        def round(self, f):
            self.rounds.append(f)
            return [f]

    for seconds, want in ((0.0, [1, 2, 3, 4]), (0.05, None)):
        e = Entry()
        with tempfile.TemporaryDirectory() as d:
            served, window_s, rounds, traced = bench.serve_window(
                e, seconds, 1, d, trace_rounds=3)
        assert traced[:2] == (2, 3) and traced[2] <= window_s
        assert served == e.rounds and rounds == len(e.rounds)
        assert e.rounds[:4] == [1, 2, 3, 4]
        if want is not None:
            assert e.rounds == want
    e = Entry()
    served, window_s, rounds, traced = bench.serve_window(e, 0.0, 1)
    assert traced is None and e.rounds == [1]


def test_serve_window_times_each_round():
    class Entry:
        def round(self, f):
            time.sleep(0.01 * f)
            return [f]

    round_ms = []
    served, window_s, rounds, _ = bench.serve_window(Entry(), 0.0, 1, None,
                                                     0, round_ms)
    assert rounds == len(round_ms) == 1 and served == [1]
    with tempfile.TemporaryDirectory() as d:
        served, window_s, rounds, _ = bench.serve_window(Entry(), 0.0, 1, d,
                                                         2, round_ms)
    assert len(round_ms) == 1 + rounds == 4
    assert round_ms[2] >= 20 and round_ms[3] >= 30
    assert sum(round_ms[1:]) <= window_s * 1e3


def test_cell_config_mix_and_metric_found_by_new_files(tmp_path):
    root = write_root(tmp_path, cells=(("tiny.new", "tiny-new"),))
    cb = root / "chipbench"
    # a new configuration, mix and metric: files plus BENCHMARK.json entries
    cfg = json.loads((cb / "configs" / "tiny.json").read_text())
    (cb / "configs" / "tiny-b.json").write_text(json.dumps(dict(cfg,
                                                                coils=2)))
    (cb / "traffic" / "tiny-new.json").write_text(json.dumps({
        "entry": "stream", "scanners": 1, "movie_frames": 2,
        "check_frames": 1, "noise": 1e-4}))
    (cb / "metrics" / "frames_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.frames)\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny-b", "source": "tests",
                         "file": "chipbench/configs/tiny-b.json",
                         "reduced": [], "why": "tiny"})
    b["workloads"][0]["config"] = "tiny-b"
    b["end_to_end"].append({"name": "frames_seen", "unit": "frames",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = bench.load_cell(root, "tiny.new")
    assert cell.cfg["coils"] == 2 and cell.mix["movie_frames"] == 2
    r = _run(root, "tiny.new")
    assert r["correct"], r["checks"]
    assert r["metrics"]["frames_seen"]["value"] == r["attempted"]


@pytest.mark.parametrize("fault", ["rejected", "shed", "degraded"])
def test_failed_or_degraded_frames_are_not_correct(tiny_root, monkeypatch,
                                                   fault):
    """A frame shed, rejected, or served after a step down the deadline
    ladder, past the compared rounds, still turns ``correct`` false."""
    from chipbench import entries
    orig = entries.ServiceEntry.round

    def round_(self, f):
        out = orig(self, f)
        if f == 3 and fault != "degraded":
            out[1] = entries.Served(out[1].scanner, f, failure=fault)
        if f == 3 and fault == "degraded":
            self.sched.events.append({"rung": 1})
        return out

    monkeypatch.setattr(entries.ServiceEntry, "round", round_)
    r = bench.run(tiny_root, "tiny.service", 2**31 + 17, 0.0, True,
                  t_start=time.perf_counter(), check_device=False)
    assert not r["correct"]
    assert r["checks"]["image_rel_l2"]["value"] <= TINY_LIMIT
    key = "degraded_steps" if fault == "degraded" else "failed_frames"
    assert r["checks"][key]["value"] == 1 > r["checks"][key]["limit"]


def test_missing_files_are_errors(tiny_root):
    with pytest.raises(bench.BenchError):
        bench.load_cell(tiny_root, "no.such.cell")
    with pytest.raises(bench.BenchError):
        bench.load_reader(tiny_root, "no_such_metric")
    with pytest.raises(bench.BenchError):
        bench.load_peak(tiny_root, "TPU v0")


def _command(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "nlinv-paper768-1chip.service-k2", "--seed",
        str(2**31 + 3), "--seconds", "1", "--trace", "0"]


def test_command_refuses_without_a_tpu():
    r = _command(ARGS, REPO)
    assert r.returncode == 2, r.stderr
    assert "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in json.loads((REPO / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _command(ARGS, tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def problem_limits(problem: str) -> list:
    """The ``image_rel_l2`` limits of the cells whose configuration names
    ``problem``: a control is held to its own problem's limits only."""
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    names = {c["name"]: json.loads((REPO / c["file"]).read_text())["problem"]
             for c in b["configs"]}
    return [json.loads((REPO / "chipbench" / "limits"
                        / f"{w['name']}.json").read_text())["image_rel_l2"]
            for w in b["workloads"] if names[w["config"]] == problem]


def test_bf16_control_fails_the_limits():
    """The NLINV control: the problem's reference computed with every
    array rounded to bfloat16, put in the program's place, reads above
    the limit of every cell of the NLINV problem (tiny size; the chip
    readings at the cells' size are in PERF.md)."""
    problem = bench.load_problem(REPO, "nlinv")
    cfg = {"n": 32, "coils": 4, "newton": 7, "cg_iters": 20,
           "assumed": {"spokes": 11, "damping": 0.9}}
    mix = {"scanners": 1, "movie_frames": 3, "noise": 1e-4}
    limits = problem_limits("nlinv")
    assert limits
    for seed in (1, 2**31 + 5, 7):
        tr = problem.make_traffic(cfg, mix, seed)
        ref, _ = problem.reference_movie(cfg, tr, 0, 3, None)
        low, _ = problem.reference_movie(cfg, tr, 0, 3, None, lowp=True)
        worst = max(reference.rel_l2(a, b) for a, b in zip(low, ref))
        assert worst > max(limits), (seed, worst)


def test_traffic_is_a_function_of_the_seed():
    from chipbench import traffic
    cfg = {"n": 16, "coils": 2, "assumed": {"spokes": 5}}
    mix = {"scanners": 2, "movie_frames": 2, "noise": 1e-4}
    a = traffic.make_traffic(cfg, mix, 2**33 + 1)
    b = traffic.make_traffic(cfg, mix, 2**33 + 1)
    c = traffic.make_traffic(cfg, mix, 2**33 + 2)
    for x, y in zip(a["movies"], b["movies"]):
        assert np.array_equal(x["y"], y["y"])
    assert not np.array_equal(a["movies"][0]["y"], c["movies"][0]["y"])
    # every seed gives the same shapes
    assert a["movies"][0]["y"].shape == c["movies"][0]["y"].shape

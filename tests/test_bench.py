"""The repro.bench subsystem: artifact schema round-trip, registry
completeness + determinism, compare-tool gating, harness discipline,
and one in-process scenario execution.

The full sweep CLI (subprocess per device count) is exercised once with
the cheapest figure; everything else runs in-process on whatever device
count the host has.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.bench import (ArtifactError, BenchContext, compare_artifacts,
                         load_artifact, make_artifact, measure, run_key,
                         scenarios, validate_artifact, write_artifact)
from repro.bench.compare import format_report
from repro.bench.compare import main as compare_main
from repro.bench.registry import DEVICE_COUNTS, SIZES

REPO = pathlib.Path(__file__).resolve().parents[1]

# benchmarks/*.py script -> the registry figure(s) it fronts; every
# script must stay a thin entry point over registered scenarios.
SCRIPT_FIGURES = {
    "fig4_algorithms.py": {"fig4"},
    "fig5_transfers.py": {"fig5"},
    "fig6_nlinv.py": {"fig6", "stream", "gridding"},
    "fig89_operators.py": {"fig89"},
    "table1_operators.py": {"table1"},
    "lm_steps.py": {"lm"},
    "serve_streams.py": {"serve"},
}

# the acceptance sweep: these figures must be registered with tiny-CI
# coverage at 1 AND 4 devices
CI_FIGURES = ("fig4", "fig5", "fig6", "fig89", "table1", "gridding",
              "stream", "serve")


def _fake_run(scenario="figX.thing", figure="figX", devices=1, size="tiny",
              steady=1.0, **kw):
    run = {"scenario": scenario, "figure": figure, "devices": devices,
           "size": size, "wall_ms": 10.0, "compile_ms": 5.0,
           "steady_ms": steady}
    run.update(kw)
    return run


# ---------------------------------------------------------------------------
# artifact schema
# ---------------------------------------------------------------------------

def test_artifact_round_trip(tmp_path):
    runs = [_fake_run(devices=1, steady=4.0),
            _fake_run(devices=4, steady=2.0),
            _fake_run(scenario="figX.other", devices=1, steady=0.5,
                      extra={"model_eff2": 1.0}, plan_cache={"steady": {}})]
    art = make_artifact(runs, sha="0" * 40, host={"platform": "cpu"})
    # speedup vs the 1-device run of the same (scenario, size)
    assert art["scenarios"]["figX.thing@d4@tiny"]["speedup_vs_1dev"] == 2.0
    assert "speedup_vs_1dev" not in art["scenarios"]["figX.thing@d1@tiny"]
    path = write_artifact(tmp_path / "a.json", art)
    assert load_artifact(path) == art
    # deterministic serialization
    assert path.read_text() == json.dumps(art, indent=2, sort_keys=True) + "\n"


def test_artifact_validation_rejects_malformed():
    good = make_artifact([_fake_run()], sha="x", host={})
    with pytest.raises(ArtifactError):
        validate_artifact({**good, "schema_version": 99})
    with pytest.raises(ArtifactError):
        validate_artifact({**good, "schema": "something-else"})
    with pytest.raises(ArtifactError):
        validate_artifact({**good, "git_sha": ""})
    run = _fake_run()
    del run["steady_ms"]
    with pytest.raises(ArtifactError, match="steady_ms"):
        make_artifact([run], sha="x", host={})
    with pytest.raises(ArtifactError, match="type"):
        make_artifact([_fake_run(steady="fast")], sha="x", host={})
    # key must match the run's own identity
    art = make_artifact([_fake_run()], sha="x", host={})
    art["scenarios"]["wrong@d1@tiny"] = art["scenarios"].pop(
        "figX.thing@d1@tiny")
    with pytest.raises(ArtifactError, match="identity"):
        validate_artifact(art)


def test_artifact_rejects_duplicate_runs():
    with pytest.raises(ArtifactError, match="duplicate"):
        make_artifact([_fake_run(), _fake_run()], sha="x", host={})


def test_artifact_load_rejects_non_json(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("not json {")
    with pytest.raises(ArtifactError, match="JSON"):
        load_artifact(p)


# ---------------------------------------------------------------------------
# compare tool
# ---------------------------------------------------------------------------

def _two_artifacts(base_steady, new_steady, **newkw):
    base = make_artifact([_fake_run(steady=base_steady)], sha="a", host={})
    new = make_artifact([_fake_run(steady=new_steady, **newkw)],
                        sha="b", host={})
    return base, new


def test_compare_pass_and_regression():
    base, new = _two_artifacts(4.0, 4.2)
    cmp = compare_artifacts(base, new, threshold_pct=25.0)
    assert cmp.ok and not cmp.regressions and cmp.unchanged

    base, new = _two_artifacts(4.0, 8.0)      # injected 2x slowdown
    cmp = compare_artifacts(base, new, threshold_pct=75.0)
    assert not cmp.ok
    assert cmp.regressions[0]["ratio"] == 2.0


def test_compare_improvement_and_noise_floor():
    base, new = _two_artifacts(4.0, 1.0)
    cmp = compare_artifacts(base, new)
    assert cmp.ok and cmp.improvements

    # both sub-floor: pure noise territory (model-only rows report 0.0)
    base, new = _two_artifacts(0.0, 0.0)
    cmp = compare_artifacts(base, new)
    assert cmp.ok and cmp.below_floor


def test_compare_sub_floor_base_cannot_hide_a_regression():
    """The base is clamped UP to the floor, not skipped: a 0.1ms row
    blowing up to 500ms must fail even though 0.1 < min_ms."""
    base, new = _two_artifacts(0.1, 500.0)
    cmp = compare_artifacts(base, new, threshold_pct=75.0, min_ms=1.0)
    assert not cmp.ok and cmp.regressions[0]["new_ms"] == 500.0
    # ...while sub-floor jitter that stays near the floor does not flake
    base, new = _two_artifacts(0.1, 0.9)
    cmp = compare_artifacts(base, new, threshold_pct=75.0, min_ms=1.0)
    assert cmp.ok and not cmp.regressions


def test_compare_gates_per_client_p95():
    """Serve scenarios: the worst-client p95 (extra.client_p95_ms) is
    gated with the same threshold — a starved client fails the compare
    even when the mean tick stayed fast."""
    p95 = lambda v: {"extra": {"client_p95_ms": v}}
    base, new = _two_artifacts(4.0, 4.0, **p95(20.0))
    base["scenarios"]["figX.thing@d1@tiny"]["extra"] = {"client_p95_ms": 8.0}
    cmp = compare_artifacts(base, new, threshold_pct=25.0)
    assert not cmp.ok and not cmp.regressions
    assert cmp.p95_regressions[0]["ratio"] == 2.5
    assert "P95 REGRESSION" in format_report(cmp)
    # within threshold: passes
    base["scenarios"]["figX.thing@d1@tiny"]["extra"] = {"client_p95_ms": 18.0}
    assert compare_artifacts(base, new, threshold_pct=25.0).ok
    # rows without the column (every non-serve scenario) are ignored
    b2, n2 = _two_artifacts(4.0, 4.0)
    assert compare_artifacts(b2, n2).p95_regressions == []
    # machine-speed calibration scales the new p95 like the steady state
    b3 = make_artifact([_fake_run(steady=4.0, **p95(10.0))], sha="a",
                       host={}, calibration_ms=1.0)
    n3 = make_artifact([_fake_run(steady=12.0, **p95(30.0))], sha="b",
                       host={}, calibration_ms=3.0)
    assert compare_artifacts(b3, n3).ok      # 3x slower host cancels out


def test_compare_normalizes_by_machine_speed():
    """A uniformly slower host moves calibration and scenarios together
    and must not regress; a code slowdown (calibration unmoved) must."""
    base = make_artifact([_fake_run(steady=4.0)], sha="a", host={},
                         calibration_ms=10.0)
    # whole sweep 3x slower (neighbor contention): 3x steady, 3x cal
    slow_host = make_artifact([_fake_run(steady=12.0)], sha="b", host={},
                              calibration_ms=30.0)
    cmp = compare_artifacts(base, slow_host, threshold_pct=75.0)
    assert cmp.ok and cmp.scale == pytest.approx(1 / 3, abs=1e-4)

    # genuine 3x code regression: steady up, calibration unchanged
    slow_code = make_artifact([_fake_run(steady=12.0)], sha="c", host={},
                              calibration_ms=10.0)
    cmp = compare_artifacts(base, slow_code, threshold_pct=75.0)
    assert not cmp.ok and cmp.regressions[0]["ratio"] == 3.0

    # artifacts without calibration compare raw (back-compat)
    nocal = make_artifact([_fake_run(steady=4.0)], sha="d", host={})
    assert compare_artifacts(nocal, nocal).scale == 1.0


def test_artifact_rejects_bad_calibration():
    with pytest.raises(ArtifactError, match="calibration"):
        make_artifact([_fake_run()], sha="x", host={}, calibration_ms=0.0)
    with pytest.raises(ArtifactError, match="calibration"):
        make_artifact([_fake_run()], sha="x", host={}, calibration_ms=-1)


def test_compare_new_and_missing_scenarios():
    one = make_artifact([_fake_run()], sha="a", host={})
    two = make_artifact([_fake_run(),
                         _fake_run(scenario="figX.added")], sha="b", host={})
    cmp = compare_artifacts(one, two)
    assert cmp.ok and cmp.new == ["figX.added@d1@tiny"]
    cmp = compare_artifacts(two, one)
    assert cmp.ok and cmp.missing == ["figX.added@d1@tiny"]


def test_compare_cli_exit_codes(tmp_path):
    """The acceptance gate: non-zero exit on an injected 2x slowdown."""
    base, new = _two_artifacts(4.0, 8.0)
    pb, pn = tmp_path / "base.json", tmp_path / "new.json"
    write_artifact(pb, base)
    write_artifact(pn, new)
    assert compare_main([str(pb), str(pn), "--threshold", "75"]) == 1
    assert compare_main([str(pb), str(pb)]) == 0
    # missing scenarios fail only when asked to
    two = make_artifact([_fake_run(steady=4.0),
                         _fake_run(scenario="figX.gone")], sha="c", host={})
    pt = tmp_path / "two.json"
    write_artifact(pt, two)
    assert compare_main([str(pt), str(pb)]) == 0
    assert compare_main([str(pt), str(pb), "--fail-on-missing"]) == 1


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_keys_deterministic_and_wellformed():
    a, b = scenarios(), scenarios()
    assert list(a) == list(b) == sorted(a)
    for key, sc in a.items():
        assert key == f"{sc.figure}.{sc.name}"
        assert set(sc.sizes) <= set(SIZES) and sc.sizes
        assert set(sc.devices) <= set(DEVICE_COUNTS) and sc.devices


def test_registry_rejects_duplicates():
    from repro.bench.registry import scenario as register
    some = next(iter(scenarios().values()))
    with pytest.raises(ValueError, match="duplicate"):
        register(some.figure, some.name)(lambda ctx: {})


def test_registry_tolerates_blank_docstrings():
    from repro.bench.registry import _REGISTRY
    from repro.bench.registry import scenario as register

    def fn(ctx):
        """   """
    register("figtest", "blank_doc")(fn)
    try:
        assert _REGISTRY["figtest.blank_doc"].doc == ""
    finally:
        del _REGISTRY["figtest.blank_doc"]


def test_registry_covers_every_benchmark_script():
    figures = {sc.figure for sc in scenarios().values()}
    for script, figs in SCRIPT_FIGURES.items():
        assert (REPO / "benchmarks" / script).exists(), script
        assert figs <= figures, f"{script}: {figs - figures} unregistered"


def test_ci_figures_cover_tiny_at_1_and_4_devices():
    by_figure = {}
    for sc in scenarios().values():
        by_figure.setdefault(sc.figure, []).append(sc)
    for fig in CI_FIGURES:
        scs = by_figure[fig]
        assert any("tiny" in sc.sizes and {1, 4} <= set(sc.devices)
                   for sc in scs), f"{fig} lacks tiny coverage at 1+4 devices"


def test_benchmark_scripts_are_thin():
    """The old per-script timing/argparse code must not creep back."""
    for script in list(SCRIPT_FIGURES) + ["run.py"]:
        text = (REPO / "benchmarks" / script).read_text()
        assert "repro.bench" in text, f"{script} bypasses repro.bench"
        assert "argparse" not in text and "perf_counter" not in text, \
            f"{script} regrew its own harness"
        assert len(text.splitlines()) < 30, f"{script} is not thin"


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

def test_measure_separates_compile_from_steady():
    import jax.numpy as jnp
    from repro.lib.plan import PlanCache

    cache = PlanCache()
    t = measure(lambda: jnp.arange(8) * 2, warmup=1, iters=4, cache=cache)
    assert t.compile_ms >= 0 and t.steady_ms >= 0
    assert t.iters == 4 and t.warmup == 1
    # steady_ms is the best (minimum) sample; percentiles sit above it
    assert t.p95_ms >= t.p50_ms >= t.steady_ms
    assert t.wall_ms >= t.compile_ms
    d = t.as_dict()
    assert d["plan_cache"]["steady"]["builds"] == 0

    with pytest.raises(ValueError):
        measure(lambda: None, iters=0)


def test_measure_reports_plan_cache_regions():
    """Setup region pays the plan build; the steady region is all hits."""
    import numpy as np
    from repro.core import Environment
    from repro.lib import fft as lfft
    from repro.lib.plan import PlanCache

    comm = Environment().subgroup(1)
    x = comm.container(np.ones((2, 8, 8), np.complex64))
    cache = PlanCache()
    t = measure(lambda: lfft.fft2_batched(x, cache=cache).data,
                warmup=1, iters=3, cache=cache)
    assert t.plan_cache["setup"]["builds"] >= 1
    assert t.plan_cache["steady"]["builds"] == 0
    assert t.plan_cache["steady"]["hit_rate"] == 1.0


def test_scenario_runs_in_process(tmp_path):
    """One real scenario through BenchContext -> schema-valid artifact."""
    from repro.core import Environment

    sc = scenarios()["gridding.plan_cold_vs_hit"]
    ctx = BenchContext(size="tiny", devices=1,
                       comm=Environment().subgroup(1),
                       out_dir=tmp_path, warmup=1, iters=2)
    res = dict(sc.fn(ctx))
    assert res["compile_ms"] > res["steady_ms"]   # cold build >> LRU hit
    run = {"scenario": sc.key, "figure": sc.figure, "devices": 1,
           "size": "tiny", **res}
    art = make_artifact([run], sha="t", host={})
    assert run_key(run) in art["scenarios"]


# ---------------------------------------------------------------------------
# sweep CLI (one subprocess, cheapest figure)
# ---------------------------------------------------------------------------

def test_compare_tooling_is_jax_free():
    """`python -m repro.bench.compare` (and artifact validation) must
    load on hosts without jax — harness/models imports stay lazy."""
    code = (
        "import sys\n"
        "sys.modules['jax'] = None   # poison: any 'import jax' raises\n"
        "import repro.bench.compare\n"
        "from repro.bench import make_artifact, validate_artifact, "
        "compare_artifacts\n"
        "run = dict(scenario='f.x', figure='f', devices=1, size='tiny',\n"
        "           wall_ms=1.0, compile_ms=1.0, steady_ms=1.0)\n"
        "art = make_artifact([run], sha='s', host={})\n"
        "assert compare_artifacts(art, art).ok\n"
        "print('jax-free OK')\n")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=60,
                       env={**os.environ, "PYTHONPATH": str(REPO / "src")})
    assert r.returncode == 0 and "jax-free OK" in r.stdout, r.stderr


def test_figure_names_match_registered_figures():
    """The sweep parent lists figures from the suite module names (no
    JAX import); that list must be exactly what the suites register."""
    from repro.bench.registry import figure_names
    assert figure_names() == tuple(sorted(
        {s.figure for s in scenarios().values()}))


def test_sweep_parent_is_jax_free():
    """The sweep parent validates ``--only`` and spawns children
    without importing JAX: an accelerator belongs to one process at a
    time, so a parent holding it would starve every child."""
    code = (
        "import sys\n"
        "sys.modules['jax'] = None   # poison: any 'import jax' raises\n"
        "from repro.bench import run\n"
        "try:\n"
        "    run.main(['--only', 'fig99', '--devices', '1'])\n"
        "except SystemExit as e:\n"
        "    print('exit:', e)\n")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=60,
                       env={**os.environ, "PYTHONPATH": str(REPO / "src")})
    assert r.returncode == 0, r.stderr
    assert "unknown figure" in r.stdout


def test_run_cli_rejects_unknown_figure(tmp_path):
    out = tmp_path / "bench.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.bench.run", "--size", "tiny",
         "--devices", "1", "--only", "fig99", "--out", str(out)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": str(REPO / "src")}, cwd=str(REPO))
    assert r.returncode != 0
    assert "unknown figure" in r.stderr
    assert not out.exists()     # a typo must never write an empty baseline


def test_run_cli_emits_valid_artifact(tmp_path):
    out = tmp_path / "bench.json"
    r = subprocess.run(
        [sys.executable, "-m", "repro.bench.run", "--size", "tiny",
         "--devices", "1", "--only", "gridding", "--out", str(out)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": str(REPO / "src")}, cwd=str(REPO))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    art = load_artifact(out)
    assert "gridding.plan_cold_vs_hit@d1@tiny" in art["scenarios"]
    assert art["host"]["device_count"] == 1

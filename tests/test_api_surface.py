"""Public-API snapshot (ISSUE 2 CI satellite).

``repro.core.__all__`` and the Environment/Communicator verb surface are
the library's stable contract; any drift (a renamed verb, a changed
parameter, a new export) must show up as an explicit diff of the
snapshots below rather than silently changing downstream code.
Runs in-process on whatever device count the host has — it inspects
signatures only.
"""

import inspect

import jax
import pytest

import repro.core as core
from repro.core import Communicator, Environment

EXPECTED_ALL = [
    "compat",
    "Environment", "Communicator",
    "DeviceGroup", "current_group", "HW", "DCN_AXES",
    "Policy", "SegmentedArray", "segment", "gather", "overlap2d_map",
    "broadcast", "scatter", "reduce", "all_reduce", "all_reduce_window",
    "vdot", "copy", "all_to_all", "reduce_scatter", "hierarchical_psum",
    "invoke_kernel", "invoke_kernel_all", "make_spmd", "PassThrough",
    "dev_rank",
    "fence", "barrier", "barrier_fence", "ordered",
]

# Every public Communicator method and its exact parameter list (the
# MPI-like verb set of paper §2.3 + p2p + container/launchers).
EXPECTED_COMMUNICATOR = {
    "container": ("self", "x", "policy", "dim", "block", "halo"),
    "bcast": ("self", "x"),
    "scatter": ("self", "x", "policy", "dim", "block", "halo"),
    "gather": ("self", "seg"),
    "allgather": ("self", "x", "dim", "axis"),
    "reduce": ("self", "seg", "op"),
    "allreduce": ("self", "x", "op", "hierarchical", "p2p", "axis"),
    "allreduce_window": ("self", "x", "window", "op", "axis", "reduce_dim",
                         "hierarchical", "window_axes", "p2p"),
    "allreduce_overlap": ("self", "x", "window", "op", "axis", "reduce_dim",
                          "window_axes", "extras", "compute", "p2p",
                          "chunks", "hierarchical"),
    "reduce_scatter": ("self", "seg", "op"),
    "alltoall": ("self", "seg", "new_dim"),
    "vdot": ("self", "x", "y", "axis", "policies"),
    "copy": ("self", "seg", "policy", "kw"),
    "send_recv": ("self", "x", "perm", "axis"),
    "shift": ("self", "x", "offset", "wrap", "axis"),
    "barrier": ("self",),
    "fence": ("self", "arrays"),
    "barrier_fence": ("self", "arrays"),
    "invoke": ("self", "fn", "args", "rank", "kw"),
    "invoke_all": ("self", "fn", "args", "kw"),
    "spmd": ("self", "fn", "in_policies", "out_policies", "check_vma",
             "donate_argnums", "jit"),
}

EXPECTED_ENVIRONMENT = {
    "group": ("self", "shape", "axes"),
    "subgroup": ("self", "n", "axes"),
    "from_mesh": ("self", "mesh"),
    "survivor": ("self", "comm", "lost"),
}

# Old free function -> its replacement (the deprecation/migration table).
EXPECTED_DEPRECATIONS = {
    "current_group": "an explicit Environment()/Communicator",
    "segment": "Communicator.container",
    "gather": "Communicator.gather / SegmentedArray.gather",
    "overlap2d_map": "SegmentedArray.halo_exchange",
    "broadcast": "Communicator.bcast",
    "scatter": "Communicator.scatter",
    "reduce": "Communicator.reduce",
    "all_reduce": "Communicator.allreduce",
    "all_reduce_window": "Communicator.allreduce_window",
    "vdot": "Communicator.vdot",
    "copy": "Communicator.copy / SegmentedArray.to",
    "all_to_all": "Communicator.alltoall",
    "reduce_scatter": "Communicator.reduce_scatter",
    "invoke_kernel": "Communicator.invoke",
    "invoke_kernel_all": "Communicator.invoke_all",
    "make_spmd": "Communicator.spmd",
    "barrier": "Communicator.barrier",
    "barrier_fence": "Communicator.barrier_fence",
}


def _param_names(fn):
    return tuple(inspect.signature(fn).parameters)


def _public_methods(cls):
    return {n for n, m in inspect.getmembers(cls, inspect.isfunction)
            if not n.startswith("_")}


def test_core_all_snapshot():
    assert list(core.__all__) == EXPECTED_ALL
    for name in EXPECTED_ALL:
        assert hasattr(core, name), f"__all__ names missing attr {name}"


def test_communicator_method_surface():
    assert _public_methods(Communicator) == set(EXPECTED_COMMUNICATOR)
    for name, params in EXPECTED_COMMUNICATOR.items():
        got = _param_names(getattr(Communicator, name))
        assert got == params, f"Communicator.{name}: {got} != {params}"


def test_environment_method_surface():
    assert _public_methods(Environment) == set(EXPECTED_ENVIRONMENT)
    for name, params in EXPECTED_ENVIRONMENT.items():
        got = _param_names(getattr(Environment, name))
        assert got == params, f"Environment.{name}: {got} != {params}"


def test_deprecation_table():
    for name, repl in EXPECTED_DEPRECATIONS.items():
        fn = getattr(core, name)
        assert getattr(fn, "__deprecated__", None) == repl, name


def test_segmented_array_fluent_surface():
    from repro.core import SegmentedArray
    fluent = {"allreduce", "allreduce_window", "allgather", "alltoall",
              "reduce", "reduce_scatter", "gather", "to", "vdot", "shift",
              "send_recv", "halo_exchange", "invoke", "astype", "seg_len",
              "segments", "with_data"}
    assert fluent <= _public_methods(SegmentedArray)


# -- the repro.lib ported-library surface (paper §4) ------------------------

EXPECTED_LIB_ALL = ["blas", "fft", "gridding", "plan",
                    "Plan", "PlanCache", "default_cache", "plan_stats"]

def test_lib_all_snapshot():
    import repro.lib as lib
    assert list(lib.__all__) == EXPECTED_LIB_ALL
    for name in EXPECTED_LIB_ALL:
        assert hasattr(lib, name)


def test_lib_ports_expose_plan_builders():
    """Every ported library exposes its plan constructor(s) and the ops
    that go through the cache (the Plan/PlanCache acceptance contract)."""
    from repro.lib import blas, fft, gridding
    for name in ("plan_fft2", "plan_fft2_batched", "fft2", "fft2_batched"):
        assert callable(getattr(fft, name)), name
    for name in ("axpy", "dot", "norm2", "gemm_batched", "gemm_ksplit",
                 "axpy_dot", "axpy_norm2", "dot_allreduce",
                 "cg_update", "xpby_dot", "tree_axpy", "tree_vdot"):
        assert callable(getattr(blas, name)), name
    for name in ("plan_gridding", "radial_trajectory", "ramlak_dcf_radial"):
        assert callable(getattr(gridding, name)), name


def test_core_fft_blas_shims_removed():
    """The repro.core.fft / repro.core.blas DeprecationWarning shims were
    removed on schedule (README PR 4); repro.lib is the only surface."""
    import importlib
    for mod in ("repro.core.fft", "repro.core.blas"):
        try:
            importlib.import_module(mod)
        except ModuleNotFoundError:
            continue
        raise AssertionError(f"{mod} should have been removed")
    assert not hasattr(core, "fft") and not hasattr(core, "blas")


# -- the repro.bench benchmark-subsystem surface (ISSUE 4) ------------------

EXPECTED_BENCH_ALL = [
    "artifact", "compare", "harness", "models", "registry",
    "SCHEMA_VERSION", "ArtifactError", "load_artifact", "make_artifact",
    "run_key", "validate_artifact", "write_artifact",
    "Comparison", "compare_artifacts",
    "BenchContext", "Timing", "measure",
    "Scenario", "scenario", "scenarios",
]

# the harness/compare contracts scenario authors and CI scripts rely on
EXPECTED_BENCH_SIGNATURES = {
    "measure": ("fn", "args", "warmup", "iters", "cache", "kw"),
    "compare_artifacts": ("base", "new", "threshold_pct", "min_ms"),
    "make_artifact": ("runs", "sha", "host", "calibration_ms"),
    "scenario": ("figure", "name", "sizes", "devices"),
}

# every artifact run row must keep exactly these required fields (the
# compare tool and CI gate key off them)
EXPECTED_ARTIFACT_REQUIRED = ["scenario", "figure", "devices", "size",
                              "wall_ms", "compile_ms", "steady_ms"]


def test_bench_all_snapshot():
    import repro.bench as bench
    assert list(bench.__all__) == EXPECTED_BENCH_ALL
    for name in EXPECTED_BENCH_ALL:
        assert hasattr(bench, name), f"__all__ names missing attr {name}"


def test_bench_signatures():
    import repro.bench as bench
    for name, params in EXPECTED_BENCH_SIGNATURES.items():
        got = _param_names(getattr(bench, name))
        assert got == params, f"repro.bench.{name}: {got} != {params}"


def test_bench_artifact_schema_fields():
    from repro.bench.artifact import REQUIRED_FIELDS, SCHEMA_VERSION
    assert SCHEMA_VERSION == 1
    assert list(REQUIRED_FIELDS) == EXPECTED_ARTIFACT_REQUIRED


def test_bench_timing_fields():
    import dataclasses

    from repro.bench import Timing
    assert [f.name for f in dataclasses.fields(Timing)] == [
        "wall_ms", "compile_ms", "steady_ms", "p50_ms", "p95_ms",
        "jitter_ms", "iters", "warmup", "plan_cache"]


# -- the repro.serve serving-layer surface (ISSUE 7) ------------------------

EXPECTED_SERVE_ALL = [
    "Engine", "Request", "make_serve_steps",
    "AdmissionError", "Rejected", "ServeConfig", "Session",
    "StreamScheduler", "Workload",
    "LMDecodeWorkload", "NlinvStreamWorkload", "SlotPool",
    "stack_carries", "unstack_carry",
]

# the scheduler contract both workloads (and any future one) code against
EXPECTED_SCHEDULER = {
    "open": ("self", "client", "meta"),
    "submit": ("self", "session", "item"),
    "tick": ("self",),
    "drain": ("self",),
    "close": ("self", "session"),
    "report": ("self",),
}

EXPECTED_WORKLOAD_HOOKS = {
    "open_session": ("self", "session"),
    "enqueue": ("self", "session", "item"),
    "step": ("self", "batch", "width"),
    "close_session": ("self", "session"),
    "set_level": ("self", "level"),
    "counters": ("self",),
}


def test_serve_all_snapshot():
    import repro.serve as serve
    assert list(serve.__all__) == EXPECTED_SERVE_ALL
    for name in EXPECTED_SERVE_ALL:
        assert hasattr(serve, name), f"__all__ names missing attr {name}"


def test_serve_scheduler_surface():
    from repro.serve import StreamScheduler, Workload
    assert _public_methods(StreamScheduler) == set(EXPECTED_SCHEDULER)
    for name, params in EXPECTED_SCHEDULER.items():
        got = _param_names(getattr(StreamScheduler, name))
        assert got == params, f"StreamScheduler.{name}: {got} != {params}"
    for name, params in EXPECTED_WORKLOAD_HOOKS.items():
        got = _param_names(getattr(Workload, name))
        assert got == params, f"Workload.{name}: {got} != {params}"


# -- the repro.task task-graph surface (ISSUE 9) ----------------------------

EXPECTED_TASK_ALL = [
    "Task", "TaskGraph", "TaskError", "CycleError", "CrossGroupError",
    "placement_token",
    "Executor", "Pipeline", "TaskRun",
]

# the contract docs/task_graph.md codes against
EXPECTED_TASK_SIGNATURES = {
    "TaskGraph.add": ("self", "name", "fn", "inputs", "outputs", "group",
                      "kind"),
    "TaskGraph.copy": ("self", "name", "fn", "inputs", "outputs", "group"),
    "TaskGraph.validate": ("self", "feeds"),
    "TaskGraph.toposort": ("self", "feeds", "_validate"),
    "Executor.run": ("self", "graph", "feeds", "outputs", "fence"),
    "Pipeline.push": ("self", "graph", "feeds", "tag", "outputs"),
    "Pipeline.flush": ("self",),
}


def test_task_all_snapshot():
    import repro.task as task
    assert list(task.__all__) == EXPECTED_TASK_ALL
    for name in EXPECTED_TASK_ALL:
        assert hasattr(task, name), f"__all__ names missing attr {name}"


def test_task_signatures():
    import repro.task as task
    for path, params in EXPECTED_TASK_SIGNATURES.items():
        cls, meth = path.split(".")
        got = _param_names(getattr(getattr(task, cls), meth))
        assert got == params, f"repro.task.{path}: {got} != {params}"


def test_task_error_hierarchy():
    from repro.task import CrossGroupError, CycleError, TaskError
    assert issubclass(CycleError, TaskError)
    assert issubclass(CrossGroupError, TaskError)
    assert issubclass(TaskError, RuntimeError)


def test_stream_engines_share_contract():
    """FramePipeline is a drop-in for FrameStream: same run signature,
    same LatencyReport artifact."""
    from repro.nlinv.stream import FramePipeline, FrameStream
    assert _param_names(FramePipeline.run) == _param_names(FrameStream.run)


# -- the repro.kernels registry surface (ISSUE 8) ---------------------------

EXPECTED_KERNELSPEC_FIELDS = [
    "family", "name", "pallas", "ref", "fallback",
    "block_args", "default_block", "block_space", "supports", "tol",
    "layout", "samples", "nsamples", "shape_case", "properties",
    "adjoint_of", "dispatch",
]

EXPECTED_REGISTRY_SIGNATURES = {
    "register": ("spec",),
    "get": ("spec_id",),
    "specs": ("family",),
    "get_impl": ("spec_id", "impl"),
    "autotune": ("spec_id", "sample", "token", "cache", "iters"),
    "choices": ("family",),
    "choices_token": ("families",),
}

# one spec per kernel op: the §4 "porting a kernel is declaring a spec"
# contract — a new family that bypasses the registry fails this snapshot
EXPECTED_SPEC_IDS = [
    "cg_fused.cg_update", "cg_fused.xpby_dot",
    "coil_mult.coil_adjoint", "coil_mult.coil_forward",
    "coil_mult.coil_lincomb", "coil_mult.plane_mult",
    "coil_mult.sms_adjoint", "coil_mult.sms_lincomb",
    "dft.dft2c",
    "flash_attention.flash_attention",
    "gridding.degrid", "gridding.grid_adjoint",
    "masked_allreduce.masked_sum",
    "mlstm.mlstm_scan",
    "rg_lru.rg_lru_scan",
]


def test_kernel_registry_surface():
    import dataclasses

    from repro.kernels import registry
    assert [f.name for f in dataclasses.fields(registry.KernelSpec)] == \
        EXPECTED_KERNELSPEC_FIELDS
    for name, params in EXPECTED_REGISTRY_SIGNATURES.items():
        got = _param_names(getattr(registry, name))
        assert got == params, f"registry.{name}: {got} != {params}"
    assert registry.PIN_ENV == "REPRO_KERNEL_BLOCKS"
    assert registry.TUNE_ENV == "REPRO_KERNEL_TUNE"


def test_kernel_registry_spec_ids():
    from repro.kernels import registry
    assert sorted(s.id for s in registry.specs()) == EXPECTED_SPEC_IDS


def test_serve_unified_scheduler():
    """Acceptance row: LM decode and NLINV streaming both run through
    the ONE StreamScheduler — the workloads are Workload subclasses and
    Engine drives the shared scheduler, with no bespoke decode loop."""
    from repro.serve import (Engine, LMDecodeWorkload, NlinvStreamWorkload,
                             Workload)
    assert issubclass(NlinvStreamWorkload, Workload)
    assert issubclass(LMDecodeWorkload, Workload)
    src = inspect.getsource(Engine)
    assert "StreamScheduler" in src and "LMDecodeWorkload" in src
    # the old bespoke driver internals are gone from the front door
    assert not hasattr(Engine, "_admit")
    assert "def _admit" not in src and "self.active" not in src


@pytest.mark.parametrize("env_dir", [None, "/var/cache/jax-shared"],
                         ids=["default", "from-env"])
def test_use_compile_cache_placement(monkeypatch, env_dir):
    """Entry points place JAX's persistent compile cache: where
    ``JAX_COMPILATION_CACHE_DIR`` is set it is left there (nothing is
    set in code), otherwise at the fixed ``.jax_cache/`` of the repo."""
    from repro.core.runtime import REPO_ROOT, use_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = use_compile_cache()
        if env_dir is None:
            assert got == str(REPO_ROOT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

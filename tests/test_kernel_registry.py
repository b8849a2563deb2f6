"""Shared kernel-registry harness (ISSUE 8 tentpole).

ONE parametrized suite replaces the per-family parity boilerplate:
``repro.kernels.registry`` auto-discovers every registered spec, and
each spec is exercised the same way —

  * pallas-vs-oracle parity on the spec's exemplar samples (interpret
    mode on CPU), to the spec's declared tolerance;
  * fallback-path equivalence: ``impl="auto"`` off-TPU must resolve to
    the spec's documented fallback and match the oracle;
  * shape/dtype contract: outputs keep the oracle's leaf shapes/dtypes;
  * dispatch/kernel block agreement (the ISSUE-8 ``bm=32`` satellite):
    the Pallas entry's default block kwargs equal the spec's
    ``default_block``, and the bespoke ``_on_tpu``/``_divisible``
    plumbing is actually gone from every family's ops module;
  * arbitrary-shape sweeps (deterministic grid always; hypothesis fuzz
    when installed): non-divisible row counts and 0-/1-row edges hit
    the documented fallback and still match the oracle;
  * per-spec properties (adjointness, epilogue consistency, block-shape
    invariance) and registry completeness;
  * autotuner mechanics: env pin -> pinned choice, forced sweep ->
    choice from the spec's space, PlanCache-backed determinism.

Adding a kernel family = registering a spec; it inherits all of this.
"""

import importlib
import inspect
import os
import pkgutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels
from repro.core.plan import PlanCache
from repro.kernels import registry

SPECS = registry.specs()
IDS = [s.id for s in SPECS]
CASES = [(s, i) for s in SPECS for i in range(s.nsamples)]
CASE_IDS = [f"{s.id}-{i}" for s, i in CASES]

# deterministic stand-in for the hypothesis sweep (hypothesis is an
# optional dev dep): divisible, non-divisible, 1-row and 0-row cases
SHAPE_GRID = [(0, 32), (1, 32), (1, 1), (7, 128), (32, 33),
              (33, 128), (70, 8), (96, 128)]


@pytest.fixture(autouse=True)
def _fresh_choices():
    registry.reset_choices()
    yield
    registry.reset_choices()


def _case(spec, i):
    out = spec.samples(i)
    args, kw, want = out[:3]
    tol = out[3] if len(out) > 3 else spec.tol
    return args, kw, want, tol


def _np(x):
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        return np.asarray(x.astype(jnp.complex64))
    return np.asarray(x.astype(jnp.float32))


def _assert_close(got, want, tol, where=""):
    gl, wl = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(gl) == len(wl), where
    for g, w in zip(gl, wl):
        assert jnp.shape(g) == jnp.shape(w), \
            f"{where}: shape {jnp.shape(g)} != {jnp.shape(w)}"
        np.testing.assert_allclose(_np(g), _np(w), rtol=10 * tol, atol=tol,
                                   err_msg=where)


# -- parity + fallback + shape/dtype contract -------------------------------

@pytest.mark.parametrize("spec,i", CASES, ids=CASE_IDS)
def test_pallas_parity(spec, i):
    """The Pallas kernel (interpret mode off-TPU) matches the jnp oracle
    on every exemplar, to the spec tolerance."""
    args, kw, want, tol = _case(spec, i)
    assert spec.supports(spec.default_block, *args, **kw), \
        "exemplar samples must be pallas-eligible"
    got = spec.dispatch(*args, impl="pallas", **kw)
    _assert_close(got, want, tol, f"{spec.id} sample {i} (pallas)")


@pytest.mark.parametrize("spec,i", CASES, ids=CASE_IDS)
def test_fallback_equivalence(spec, i):
    """``impl='auto'`` off-TPU resolves to the spec's documented
    fallback and is numerically equivalent to the oracle."""
    args, kw, want, tol = _case(spec, i)
    impl, block = spec.resolve("auto", None, *args, **kw)
    if not registry.on_tpu():
        assert impl == spec.fallback, \
            f"{spec.id}: auto off-TPU resolved to {impl}"
        assert block == spec.default_block
    got = spec.dispatch(*args, impl="auto", **kw)
    _assert_close(got, want, tol, f"{spec.id} sample {i} ({impl})")


@pytest.mark.parametrize("spec", SPECS, ids=IDS)
def test_tally_records_resolved_impl(spec):
    """The trace-time tally names what each dispatch resolved to:
    off-TPU, ``auto`` takes the fallback and an explicit ``pallas`` runs
    in interpret mode; on a TPU both are the compiled kernel."""
    args, kw, _, _ = _case(spec, 0)
    registry.reset_tally()
    spec.resolve("auto", None, *args, **kw)
    spec.resolve("pallas", None, *args, **kw)
    want = ({"pallas": 2} if registry.on_tpu()
            else {spec.fallback: 1, "pallas-interpret": 1})
    assert registry.tally() == {spec.id: want}


@pytest.mark.parametrize("spec,i", CASES, ids=CASE_IDS)
def test_output_dtypes_match_oracle(spec, i):
    args, kw, want, _ = _case(spec, i)
    got = spec.dispatch(*args, impl="pallas", **kw)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.asarray(g).dtype == jnp.asarray(w).dtype, spec.id


# -- arbitrary shapes hit the fallback and stay correct ---------------------

SHAPE_SPECS = [s for s in SPECS if s.shape_case is not None]
SHAPE_CASES = [(s, m, y) for s in SHAPE_SPECS for (m, y) in SHAPE_GRID]


@pytest.mark.parametrize(
    "spec,m,y", SHAPE_CASES,
    ids=[f"{s.id}-{m}x{y}" for s, m, y in SHAPE_CASES])
def test_arbitrary_shapes_fallback_and_match(spec, m, y):
    """Non-divisible/0-/1-row operand shapes: ``auto`` must route to the
    documented fallback off-TPU (never trip a kernel assert) and match
    the oracle to spec tolerance."""
    case = spec.shape_case(m * 1000 + y, m, y)
    if case is None:
        return                       # the draw is meaningless for the family
    args, kw, want = case[:3]
    impl, block = spec.resolve("auto", None, *args, **kw)
    if not registry.on_tpu():
        assert impl == spec.fallback
    got = spec.dispatch(*args, impl="auto", **kw)
    _assert_close(got, want, case[3] if len(case) > 3 else spec.tol,
                  f"{spec.id} shape ({m},{y})")
    # explicit pallas on an unsupported shape degrades safely too
    if not spec.supports(spec.default_block, *args, **kw):
        got2 = spec.dispatch(*args, impl="pallas", **kw)
        _assert_close(got2, want, case[3] if len(case) > 3 else spec.tol,
                      f"{spec.id} shape ({m},{y}) pallas-degrade")


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st

    @pytest.mark.parametrize("spec", SHAPE_SPECS,
                             ids=[s.id for s in SHAPE_SPECS])
    @given(m=st.integers(0, 96), y=st.integers(1, 144),
           seed=st.integers(0, 3))
    @settings(max_examples=12, deadline=None,
              suppress_health_check=[HealthCheck.function_scoped_fixture])
    def test_hypothesis_shapes_fallback_and_match(spec, m, y, seed):
        case = spec.shape_case(seed, m, y)
        if case is None:
            return
        args, kw, want = case[:3]
        impl, _ = spec.resolve("auto", None, *args, **kw)
        if not registry.on_tpu():
            assert impl == spec.fallback
        got = spec.dispatch(*args, impl="auto", **kw)
        _assert_close(got, want, case[3] if len(case) > 3 else spec.tol,
                      f"{spec.id} hyp ({m},{y})")
except ImportError:                             # optional dev dependency
    pass


# -- per-spec properties (adjointness, epilogues, invariances) --------------

PROPS = [(s, j) for s in SPECS for j in range(len(s.properties))]


@pytest.mark.parametrize("spec,j", PROPS,
                         ids=[f"{s.id}-prop{j}" for s, j in PROPS])
def test_spec_properties(spec, j):
    spec.properties[j]()


def test_adjoint_pairs_linked():
    """Specs declaring ``adjoint_of`` point at a registered spec of the
    same family (the gridding degrid/grid pair; adjointness itself is a
    spec property)."""
    pairs = [s for s in SPECS if s.adjoint_of]
    assert pairs, "expected at least the gridding adjoint pair"
    for s in pairs:
        other = registry.get(s.adjoint_of)
        assert other.family == s.family


# -- single source of truth for block shapes (the bm=32 satellite) ----------

@pytest.mark.parametrize("spec", SPECS, ids=IDS)
def test_dispatch_and_kernel_agree_on_blocks(spec):
    """The Pallas entry's default block kwargs ARE the spec's
    ``default_block`` — dispatch eligibility and the kernel's internal
    divisibility assert can never drift apart again."""
    sig = inspect.signature(spec.pallas)
    for arg, val in zip(spec.block_args, spec.default_block):
        assert sig.parameters[arg].default == val, \
            f"{spec.id}: kernel default {arg}=" \
            f"{sig.parameters[arg].default} != spec {val}"
    assert spec.default_block in spec.block_space
    assert all(len(b) == len(spec.block_args) for b in spec.block_space)


@pytest.mark.parametrize("family", sorted({s.family for s in SPECS}))
def test_bespoke_dispatch_plumbing_deleted(family):
    """The hand-rolled per-family backend plumbing is gone: ops modules
    define no ``_on_tpu``/``_divisible``/``_split``/``_planes`` of their
    own — the registry helpers are the single copy."""
    mod = importlib.import_module(f"repro.kernels.{family}.ops")
    src = inspect.getsource(mod)
    for name in ("def _on_tpu", "def _divisible", "def _split",
                 "def _planes"):
        assert name not in src, f"{family}.ops still defines {name}"


# -- completeness + factory surface -----------------------------------------

def test_registry_covers_every_family():
    """Every ``kernels/`` subpackage registers at least one spec, and
    every spec's family is a real subpackage (auto-discovery is total)."""
    pkg_dir = os.path.dirname(repro.kernels.__file__)
    subpkgs = {m.name for m in pkgutil.iter_modules([pkg_dir]) if m.ispkg}
    families = {s.family for s in SPECS}
    assert families == subpkgs, (families, subpkgs)


def test_get_impl_factory():
    fn = registry.get_impl("cg_fused.xpby_dot", impl="jnp")
    args, kw, want, tol = _case(registry.get("cg_fused.xpby_dot"), 0)
    _assert_close(fn(*args, **kw), want, tol, "get_impl")
    with pytest.raises(KeyError):
        registry.get("no_such.spec")


# -- autotuner mechanics ----------------------------------------------------

def test_autotune_default_off_tpu(monkeypatch):
    """Without a pin or forced sweep, off-TPU resolution is the spec
    default (never a sweep of interpret-mode kernels), cached in the
    tune PlanCache with zero steady-state rebuilds."""
    monkeypatch.delenv(registry.PIN_ENV, raising=False)
    monkeypatch.delenv(registry.TUNE_ENV, raising=False)
    spec = registry.get("cg_fused.cg_update")
    args, kw, _, _ = _case(spec, 0)
    cache = PlanCache()
    b1 = registry.autotune(spec.id, sample=lambda: (args, kw),
                           token=("t", 32), cache=cache)
    b2 = registry.autotune(spec.id, sample=lambda: (args, kw),
                           token=("t", 32), cache=cache)
    assert b1 == b2 == spec.default_block
    assert cache.misses == 1 and cache.hits == 1
    assert registry.choices("cg_fused")[spec.id]["source"] == "default"


def test_autotune_env_pin(monkeypatch):
    """REPRO_KERNEL_BLOCKS pins both the autotuner and trace-time
    ``block=None`` resolution — the deterministic-CI switch."""
    spec = registry.get("cg_fused.cg_update")
    monkeypatch.setenv(registry.PIN_ENV, "cg_fused.cg_update=64")
    assert registry.pinned_block(spec) == (64,)
    assert spec.pick_block(None) == (64,)
    cache = PlanCache()
    b = registry.autotune(spec.id, token=("pin",), cache=cache)
    assert b == (64,)
    assert registry.choices("cg_fused")[spec.id] == \
        {"block": "64", "source": "pinned"}
    # the global pin form
    monkeypatch.setenv(registry.PIN_ENV, "default")
    assert registry.pinned_block(spec) == spec.default_block
    # pins are part of the tune key: no stale reuse across pin changes
    b2 = registry.autotune(spec.id, token=("pin",), cache=cache)
    assert b2 == spec.default_block and cache.misses == 2


def test_autotune_forced_sweep(monkeypatch):
    """REPRO_KERNEL_TUNE=1 forces a real sweep even off-TPU: the winner
    comes from the spec's block space and the timing table lands in the
    cached plan meta."""
    monkeypatch.delenv(registry.PIN_ENV, raising=False)
    monkeypatch.setenv(registry.TUNE_ENV, "1")
    spec = registry.get("masked_allreduce.masked_sum")
    args, kw, _, _ = _case(spec, 0)
    cache = PlanCache()
    b = registry.autotune(spec.id, sample=lambda: (args, kw),
                          token=("sweep",), cache=cache, iters=1)
    assert b in spec.block_space
    assert registry.choices()[spec.id]["source"] == "swept"
    key = ("kernel_tune", spec.id, jax.default_backend(), ("sweep",), None)
    plan = cache.get_or_build(key, lambda: pytest.fail("must be cached"))
    assert plan.meta["table"], "sweep must record per-candidate timings"
    # the swept winner becomes the trace-time choice and the token
    assert spec.pick_block(None) == b
    assert (spec.id, b) in registry.choices_token(("masked_allreduce",))

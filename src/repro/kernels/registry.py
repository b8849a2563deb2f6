"""Declarative kernel-family registry + block-size autotuner (paper §4).

The §4 framework claim — porting new GPU kernels is cheap — rests on
every Pallas family sharing ONE dispatch/test surface instead of each
hand-rolling its own ``_on_tpu``/``_planes``/divisibility plumbing and
hard-coding block shapes.  A :class:`KernelSpec` declares, per kernel
op:

  * the planes/layout contract (``layout``) and the Pallas entry
    (``pallas``) with its named block arguments (``block_args``);
  * the block-shape space the autotuner may sweep (``block_space``)
    and the default choice (``default_block``) — the single source of
    truth the dispatch divisibility check is derived from (the
    ``bm=32`` constant that used to live in both ``cg_fused/ops.py``
    and ``cg_fused/kernel.py``);
  * the jnp ref oracle (``ref``), the CPU fallback rule (``fallback``:
    the impl name ``auto`` routes to off-TPU or when ``supports`` says
    the operands don't tile), and the parity tolerance (``tol``);
  * exemplar inputs (``samples``) and arbitrary-shape generators
    (``shape_case``) that the shared harness in
    ``tests/test_kernel_registry.py`` discovers and sweeps — one
    parametrized parity/fallback/property suite for every family.

Block-size autotuning is a *plan-build* concern (the MGPU plan idiom:
decide once, execute per frame): :func:`autotune` sweeps a spec's block
space on the live backend, caches the winner in a PlanCache keyed on
(spec, backend, shape token, pin), and records it as the spec's current
choice so both plan keys (:func:`choices_token`) and bench artifacts
(:func:`choices`) expose it.  ``REPRO_KERNEL_BLOCKS`` pins choices for
deterministic CI (``default`` pins every spec to its default, or
``family.op=AxB,...`` per spec); ``REPRO_KERNEL_TUNE=1`` forces sweeps
even off-TPU (interpret mode — test/diagnostic use only).
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import math
import os
import pkgutil
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..core.plan import Plan, PlanCache

PIN_ENV = "REPRO_KERNEL_BLOCKS"
TUNE_ENV = "REPRO_KERNEL_TUNE"


# ---------------------------------------------------------------------------
# shared backend/plane helpers — the ONE copy of the per-family plumbing
# ---------------------------------------------------------------------------

def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def split(x):
    """Complex array -> (re, im) f32 planes."""
    return jnp.real(x).astype(jnp.float32), jnp.imag(x).astype(jnp.float32)


def planes(x):
    """Complex (..., Y) -> two (M, Y) f32 row planes (the re/im VREG
    layout every row-blocked kernel family shares)."""
    y = x.shape[-1]
    return [v.reshape(-1, y) for v in split(x)]


def rows(x) -> int:
    """Flattened row count of the (..., Y) -> (M, Y) plane layout."""
    return math.prod(x.shape[:-1])


def rows_divisible(x, bm: int, min_ndim: int = 2) -> bool:
    """THE row-block eligibility rule: flattened rows positive and
    divisible by ``min(bm, rows)`` — mirrors the kernels' own
    ``assert M % bm == 0`` after their ``bm = min(bm, M)`` clamp, so
    dispatch and kernel agree by construction (0 rows never tile)."""
    m = rows(x)
    return x.ndim >= min_ndim and m > 0 and m % min(bm, m) == 0


def dim_divisible(n: int, b: int) -> bool:
    """Single-dimension form of the same clamp-then-divide rule."""
    return n > 0 and n % min(b, n) == 0


# ---------------------------------------------------------------------------
# KernelSpec + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class KernelSpec:
    """One registered kernel op: contract, entries, block space, oracle.

    ``supports(block, *args, **kw)`` receives the *dispatch-level*
    operands and decides Pallas eligibility for a concrete block choice;
    ``samples(i)`` returns ``(args, kw, want[, tol])`` exemplars for the
    shared harness; ``shape_case(seed, m, y)`` maps an arbitrary
    (rows, lanes) draw onto family-appropriate operands (or None when
    the draw is meaningless for the family); ``properties`` are
    zero-argument invariant checks (adjointness, epilogue consistency,
    block invariance) the harness runs per spec.
    """

    family: str
    name: str
    pallas: Callable
    ref: Callable
    fallback: str
    block_args: tuple
    default_block: tuple
    block_space: tuple
    supports: Callable
    tol: float
    layout: str = ""
    samples: Callable | None = None
    nsamples: int = 2
    shape_case: Callable | None = None
    properties: tuple = ()
    adjoint_of: str | None = None
    dispatch: Callable | None = None

    @property
    def id(self) -> str:
        return f"{self.family}.{self.name}"

    def pick_block(self, block) -> tuple:
        """Explicit caller block > env pin > current (tuned) choice >
        spec default.  Trace-safe: pure Python on static shapes."""
        if block is not None:
            b = (block,) if isinstance(block, int) else tuple(block)
            if len(b) != len(self.block_args):
                raise ValueError(
                    f"{self.id}: block {b} != arity of {self.block_args}")
            return b
        pin = pinned_block(self)
        if pin is not None:
            return pin
        return current_block(self)

    def resolve(self, impl: str, block, *args, **kw):
        """Resolve ``(impl, block)`` for dispatch: ``auto`` runs Pallas
        on TPU when the operands tile, else the declared fallback; an
        explicit ``pallas`` also degrades to the fallback on shapes the
        kernel cannot tile (never an assert on the hot path)."""
        block = self.pick_block(block)
        if impl == "auto":
            impl = ("pallas" if on_tpu() and self.supports(block, *args, **kw)
                    else self.fallback)
        elif impl == "pallas" and not self.supports(block, *args, **kw):
            impl = self.fallback
        # the dispatch entries run Pallas with ``interpret=not on_tpu()``
        label = impl if impl != "pallas" or on_tpu() else "pallas-interpret"
        with _LOCK:
            _TALLY[(self.id, label)] += 1
        return impl, block

    def block_kw(self, block) -> dict:
        """The chosen block as the Pallas entry's keyword arguments."""
        return dict(zip(self.block_args, block))


_REGISTRY: dict[str, KernelSpec] = {}
_CHOICES: dict[str, dict] = {}
# (spec id, impl label) -> resolutions; labels: "pallas" (compiled
# Mosaic), "pallas-interpret", or the spec's fallback name
_TALLY: collections.Counter = collections.Counter()
_LOCK = threading.Lock()
_TUNE_CACHE = PlanCache(maxsize=512)
_ensured = False


def register(spec: KernelSpec) -> KernelSpec:
    """Register a spec (idempotent per id; last registration wins)."""
    with _LOCK:
        _REGISTRY[spec.id] = spec
    return spec


def _ensure_all() -> None:
    """Import every ``kernels/`` subpackage so registration is complete
    (auto-discovery: a new family registers by merely existing)."""
    global _ensured
    if _ensured:
        return
    pkg_dir = os.path.dirname(__file__)
    for m in pkgutil.iter_modules([pkg_dir]):
        if m.ispkg:
            importlib.import_module(f"{__package__}.{m.name}")
    _ensured = True


def get(spec_id: str) -> KernelSpec:
    _ensure_all()
    try:
        return _REGISTRY[spec_id]
    except KeyError:
        raise KeyError(f"unknown kernel spec {spec_id!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def specs(family: str | None = None) -> list[KernelSpec]:
    _ensure_all()
    out = [s for s in _REGISTRY.values()
           if family is None or s.family == family]
    return sorted(out, key=lambda s: s.id)


def get_impl(spec_id: str, impl: str = "auto") -> Callable:
    """The spec's dispatch entry with the impl pre-bound — the factory
    the model/solver layers call instead of importing family modules."""
    spec = get(spec_id)
    if spec.dispatch is None:
        raise ValueError(f"{spec_id} has no dispatch attached")

    def bound(*args, **kw):
        kw.setdefault("impl", impl)
        return spec.dispatch(*args, **kw)

    bound.__name__ = f"{spec.name}[{impl}]"
    return bound


# ---------------------------------------------------------------------------
# pinning + current choices
# ---------------------------------------------------------------------------

def pinned_block(spec: KernelSpec) -> tuple | None:
    """The env-pinned block for a spec, or None.  ``default`` pins every
    spec to its default; ``family.op=AxB`` pins one spec."""
    raw = os.environ.get(PIN_ENV, "").strip()
    if not raw:
        return None
    if raw == "default":
        return spec.default_block
    for part in raw.split(","):
        name, _, val = part.partition("=")
        if name.strip() == spec.id and val:
            b = tuple(int(v) for v in val.split("x"))
            if len(b) != len(spec.block_args):
                raise ValueError(f"{PIN_ENV} pin {part!r}: expected "
                                 f"{len(spec.block_args)} dims "
                                 f"({spec.block_args})")
            return b
    return None


def current_block(spec: KernelSpec) -> tuple:
    """Pin > last autotuned choice > spec default."""
    pin = pinned_block(spec)
    if pin is not None:
        return pin
    with _LOCK:
        c = _CHOICES.get(spec.id)
    return tuple(c["block"]) if c else spec.default_block


def choices(family: str | None = None) -> dict:
    """JSON-able snapshot of every (selected) spec's current block
    choice and where it came from — what bench scenarios put in
    ``extra.kernel_blocks``."""
    out = {}
    for spec in specs(family):
        pin = pinned_block(spec)
        with _LOCK:
            c = _CHOICES.get(spec.id)
        if pin is not None:
            blk, src = pin, "pinned"
        elif c is not None:
            blk, src = tuple(c["block"]), c["source"]
        else:
            blk, src = spec.default_block, "default"
        out[spec.id] = {"block": "x".join(str(v) for v in blk),
                        "source": src}
    return out


def choices_token(families) -> tuple:
    """Hashable (spec id, current block) pairs for the given families —
    plan keys include this so a changed tuning choice (or pin) builds a
    distinct plan instead of silently reusing a stale one."""
    toks = []
    for fam in families:
        for spec in specs(fam):
            toks.append((spec.id, current_block(spec)))
    return tuple(sorted(toks))


def reset_choices() -> None:
    """Drop recorded choices (tests); pins and the tune cache remain."""
    with _LOCK:
        _CHOICES.clear()


def tally() -> dict:
    """Which impl every dispatch resolved to since :func:`reset_tally`:
    ``{spec id: {label: count}}``.  Resolution happens at trace time, so
    a count is one traced call site, not one execution — what it proves
    is which code a compiled program contains (Mosaic kernel, interpret
    mode, or the jnp fallback)."""
    out: dict[str, dict[str, int]] = {}
    with _LOCK:
        for (sid, label), n in sorted(_TALLY.items()):
            out.setdefault(sid, {})[label] = n
    return out


def reset_tally() -> None:
    with _LOCK:
        _TALLY.clear()


def tune_cache() -> PlanCache:
    """The PlanCache backing the autotuner (its hit/miss counters are
    the 'zero steady-state rebuilds' evidence)."""
    return _TUNE_CACHE


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def autotune(spec_id: str, sample: Callable | None = None, *,
             token: tuple = (), cache: PlanCache | None = None,
             iters: int = 3) -> tuple:
    """Resolve (and on TPU, sweep) the block choice for a spec at one
    problem geometry.

    ``sample`` is a zero-arg thunk returning ``(args, kw)`` concrete
    operands — only invoked when a sweep actually runs, so callers may
    pass a lazily-built zeros payload.  ``token`` is the hashable
    geometry identity the sweep result is cached under.  Pinned specs
    and off-TPU backends resolve immediately (pin / default) — sweeps
    of interpret-mode kernels would measure the interpreter, not the
    hardware — unless ``REPRO_KERNEL_TUNE=1`` forces one.  The winner
    is recorded as the spec's current choice (see
    :func:`current_block` / :func:`choices_token`).
    """
    spec = get(spec_id)
    cache = _TUNE_CACHE if cache is None else cache
    pin = pinned_block(spec)
    backend = jax.default_backend()
    key = ("kernel_tune", spec.id, backend, tuple(token), pin)

    def build():
        table: dict[str, float] = {}
        if pin is not None:
            choice, source = pin, "pinned"
        elif (sample is None or len(spec.block_space) <= 1
              or not (on_tpu() or os.environ.get(TUNE_ENV, "0") == "1")):
            choice, source = spec.default_block, "default"
        else:
            args, kw = sample()
            cands = [b for b in spec.block_space
                     if spec.supports(tuple(b), *args, **kw)]
            for b in cands:
                b = tuple(b)
                run = lambda: spec.dispatch(*args, impl="pallas",
                                            block=b, **kw)
                jax.block_until_ready(run())          # compile outside
                best = float("inf")
                for _ in range(iters):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run())
                    best = min(best, time.perf_counter() - t0)
                table["x".join(str(v) for v in b)] = round(best * 1e3, 4)
            if table:
                win = min(table, key=table.get)
                choice = tuple(int(v) for v in win.split("x"))
                source = "swept"
            else:
                choice, source = spec.default_block, "unsupported"
        return Plan.value(key, tuple(choice),
                          lib="kernels", op=f"tune.{spec.id}",
                          meta={"block": tuple(choice), "source": source,
                                "table": table})

    plan = cache.get_or_build(key, build)
    choice = tuple(plan.meta["block"])
    with _LOCK:
        _CHOICES[spec.id] = {"block": choice,
                             "source": plan.meta["source"]}
    return choice

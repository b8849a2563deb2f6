"""libblas port — plan-cached segmented BLAS (paper §4, Fig. 4).

MGPU's libblas consolidates CUBLAS under the segmented-container
interface; the port here adds the plan layer: every operation is a
:class:`repro.lib.plan.Plan` keyed on the operand layout (shape, dtype,
policy, group), compiled once and cached.  On top of the paper's
verb-per-op set it provides the two fused epilogues a CG-style solver
actually wants on the hot path:

``axpy_dot``       w = a*x + y and <z, w> in ONE compiled program (the
                   classic fused AXPY+DOT epilogue — saves a full pass
                   over w);
``dot_allreduce``  shard-local partial products + the cross-segment
                   reduction fused into one SPMD program (paper Table 1:
                   'scalar products of all data' pay exactly one
                   all-reduce).

Scaling behaviour matches paper Fig. 4: ``axpy``/``gemm_batched`` are
segment-local (linear scaling), ``dot``/``norm2`` add one reduction,
``gemm_ksplit`` adds the inter-device reduction of the contracted dim.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import comm as _comm
from ..core.comm import _axis_arg
from ..core.segmented import Policy, SegmentedArray
from ..kernels import registry as _kreg
from ..kernels.cg_fused import ops as _cg_ops
from .plan import Plan, PlanCache, default_cache, seg_token


def _cache(cache):
    return default_cache() if cache is None else cache


def _binary_plan(op: str, x: SegmentedArray, y: SegmentedArray,
                 builder, cache: PlanCache | None,
                 extra: tuple = ()) -> Plan:
    cache = _cache(cache)
    key = ("blas", op, seg_token(x), seg_token(y), *extra)
    return cache.get_or_build(
        key, lambda: Plan(key=key, fn=builder(), lib="blas", op=op))


# ---------------------------------------------------------------------------
# tree-level math (plain arrays / tracers) — the ONE implementation the
# segmented plans below and nlinv's pytree algebra (operators.uaxpy/udot)
# both route through, so single-device and distributed paths share it.
# ---------------------------------------------------------------------------

def tree_axpy(a, x, y):
    """``a*x + y`` over matching pytrees of plain arrays (jit/shard_map
    safe — the in-program form of :func:`axpy`)."""
    return jax.tree.map(lambda u, v: a * u + v, x, y)


def tree_vdot(x, y):
    """Conjugating inner product summed over all leaves of matching
    pytrees (the in-program form of :func:`dot`; callers inject the
    cross-segment reduction, e.g. ``Communicator.vdot``)."""
    xl, xdef = jax.tree.flatten(x)
    yl, ydef = jax.tree.flatten(y)
    if xdef != ydef:
        raise ValueError(f"tree_vdot operands differ in structure: "
                         f"{xdef} vs {ydef}")
    return sum(jnp.vdot(a, b) for a, b in zip(xl, yl))


# ---------------------------------------------------------------------------
# level-1: axpy / dot / norm2 (+ fused epilogues)
# ---------------------------------------------------------------------------

def axpy(a, x: SegmentedArray, y: SegmentedArray,
         cache: PlanCache | None = None) -> SegmentedArray:
    """a*X + Y, segment-local (the strong-scaling op of paper Fig. 4).
    ``a`` is a runtime scalar — it does not key the plan."""
    plan = _binary_plan("axpy", x, y,
                        lambda: jax.jit(tree_axpy),
                        cache)
    return y.with_data(plan(jnp.asarray(a), x.data, y.data))


def dot(x: SegmentedArray, y: SegmentedArray,
        cache: PlanCache | None = None) -> jax.Array:
    """<x, y> (conjugating) with one reduction across segments."""
    plan = _binary_plan("dot", x, y,
                        lambda: jax.jit(tree_vdot),
                        cache)
    return plan(x.data, y.data)


def norm2(x: SegmentedArray, cache: PlanCache | None = None) -> jax.Array:
    """||x||^2 = Re <x, x>."""
    plan = _binary_plan("norm2", x, x,
                        lambda: jax.jit(
                            lambda xd: jnp.real(jnp.vdot(xd, xd))),
                        cache)
    return plan(x.data)


def axpy_dot(a, x: SegmentedArray, y: SegmentedArray, z: SegmentedArray,
             cache: PlanCache | None = None):
    """Fused epilogue: ``w = a*x + y`` and ``<z, w>`` in one compiled
    program (one pass over ``w`` instead of two).  Returns ``(w, <z, w>)``.

    The CG update pair ``r -= alpha*Ap; rs = <r, r>`` is
    ``axpy_dot(-alpha, Ap, r, z=r_new)`` territory — pass ``z=x`` aliases
    freely, everything is functional.
    """
    def build():
        def fused(a_, xd, yd, zd):
            w = a_ * xd + yd
            return w, jnp.vdot(zd, w)
        return jax.jit(fused)

    plan = _binary_plan("axpy_dot", x, y, build, cache,
                        extra=(seg_token(z),))
    w, d = plan(jnp.asarray(a), x.data, y.data, z.data)
    return y.with_data(w), d


def axpy_norm2(a, x: SegmentedArray, y: SegmentedArray,
               cache: PlanCache | None = None):
    """Fused ``w = a*x + y`` and ``||w||^2`` (the CG residual update)."""
    def build():
        def fused(a_, xd, yd):
            w = a_ * xd + yd
            return w, jnp.real(jnp.vdot(w, w))
        return jax.jit(fused)

    plan = _binary_plan("axpy_norm2", x, y, build, cache)
    w, n = plan(jnp.asarray(a), x.data, y.data)
    return y.with_data(w), n


def _is_seg(leaf):
    return isinstance(leaf, SegmentedArray)


def _seg_leaves(tree, name):
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_seg)
    if not leaves or not all(_is_seg(l) for l in leaves):
        raise ValueError(f"{name} operands must be (pytrees of) "
                         f"SegmentedArrays")
    return leaves, treedef


def cg_update(alpha, p, ap, x, r, cache: PlanCache | None = None):
    """Fused single-pass CG update over (pytrees of) containers:
    ``x' = x + alpha*p``, ``r' = r - alpha*Ap`` and the residual
    dot-product epilogue ``rs = sum |r'|^2`` — the three-pass unfused
    body collapsed into one program (``kernels.cg_fused``; the Pallas
    kernels on TPU, the same single-expression fusion via XLA
    elsewhere).  Returns ``(x', r', rs)``.

    The epilogue follows the same reduction contract as
    ``Communicator.vdot``: on the logical container data the global
    contraction already spans all shards, so no explicit collective is
    added and CLONE leaves count once.
    """
    cache = _cache(cache)
    pl_, pdef = _seg_leaves(p, "cg_update")
    apl, _ = _seg_leaves(ap, "cg_update")
    xl, _ = _seg_leaves(x, "cg_update")
    rl, rdef = _seg_leaves(r, "cg_update")
    n = len(xl)
    # resolve (and on TPU, sweep) the row-block choice on the biggest
    # leaf at plan-build time; the winner is part of the plan identity
    big = max(pl_, key=lambda l: l.data.size)
    blocks = _kreg.autotune(
        "cg_fused.cg_update",
        sample=lambda: ((jnp.float32(0.5), big.data, big.data,
                         big.data, big.data), {}),
        token=("blas", seg_token(big)))
    key = ("blas", "cg_update", tuple(seg_token(l) for l in xl),
           tuple(seg_token(l) for l in pl_), blocks)

    def build():
        def fused(a_, *flat):
            ps, aps = flat[:n], flat[n:2 * n]
            xs, rs = flat[2 * n:3 * n], flat[3 * n:]
            outs = [_cg_ops.cg_update(a_, p_, ap_, x_, r_, block=blocks)
                    for p_, ap_, x_, r_ in zip(ps, aps, xs, rs)]
            return ([o[0] for o in outs], [o[1] for o in outs],
                    sum(o[2] for o in outs))
        return Plan(key=key, fn=jax.jit(fused), lib="blas", op="cg_update",
                    meta={"kernel_blocks": {"cg_fused.cg_update": blocks}})

    plan = cache.get_or_build(key, build)
    x2, r2, rs = plan(jnp.asarray(alpha),
                      *[l.data for l in pl_], *[l.data for l in apl],
                      *[l.data for l in xl], *[l.data for l in rl])
    x_out = jax.tree.unflatten(pdef, [s.with_data(d)
                                      for s, d in zip(xl, x2)])
    r_out = jax.tree.unflatten(rdef, [s.with_data(d)
                                      for s, d in zip(rl, r2)])
    return x_out, r_out, rs


def xpby_dot(x, y, beta, cache: PlanCache | None = None):
    """Fused ``w = x + beta*y`` with the ``sum |w|^2`` epilogue over
    (pytrees of) containers — the CG search-direction step
    ``p = r + beta*p`` in one pass.  Returns ``(w, d)``."""
    cache = _cache(cache)
    xl, xdef = _seg_leaves(x, "xpby_dot")
    yl, _ = _seg_leaves(y, "xpby_dot")
    n = len(xl)
    big = max(xl, key=lambda l: l.data.size)
    blocks = _kreg.autotune(
        "cg_fused.xpby_dot",
        sample=lambda: ((big.data, big.data, jnp.float32(0.5)), {}),
        token=("blas", seg_token(big)))
    key = ("blas", "xpby_dot", tuple(seg_token(l) for l in xl),
           tuple(seg_token(l) for l in yl), blocks)

    def build():
        def fused(b_, *flat):
            xs, ys = flat[:n], flat[n:]
            outs = [_cg_ops.xpby_dot(x_, y_, b_, block=blocks)
                    for x_, y_ in zip(xs, ys)]
            return [o[0] for o in outs], sum(o[1] for o in outs)
        return Plan(key=key, fn=jax.jit(fused), lib="blas", op="xpby_dot",
                    meta={"kernel_blocks": {"cg_fused.xpby_dot": blocks}})

    plan = cache.get_or_build(key, build)
    w, d = plan(jnp.asarray(beta),
                *[l.data for l in xl], *[l.data for l in yl])
    w_out = jax.tree.unflatten(xdef, [s.with_data(v)
                                      for s, v in zip(xl, w)])
    return w_out, d


def dot_allreduce(x: SegmentedArray, y: SegmentedArray,
                  cache: PlanCache | None = None) -> jax.Array:
    """<x, y> with the shard-local partial product and the cross-segment
    psum fused into one SPMD program (the paper's 'one inter-device
    reduction' per scalar product, scheduled explicitly rather than left
    to XLA's resharding of the global vdot)."""
    def build():
        # capture only scalars/specs in the kernel closure — capturing
        # the SegmentedArray itself would pin its device buffer inside
        # the long-lived plan cache.
        ax = _axis_arg(x.mesh_axes)
        is_clone = x.policy is Policy.CLONE

        def body(xl, yl):
            part = jnp.vdot(xl, yl)
            return part if is_clone else lax.psum(part, ax)

        sm = jax.shard_map(body, mesh=x.group.mesh,
                           in_specs=(x.pspec, y.pspec), out_specs=P())
        return jax.jit(sm)

    plan = _binary_plan("dot_allreduce", x, y, build, cache)
    return plan(x.data, y.data)


# ---------------------------------------------------------------------------
# level-3: batched / k-split GEMM
# ---------------------------------------------------------------------------

def gemm_batched(a: SegmentedArray, b: SegmentedArray,
                 cache: PlanCache | None = None) -> SegmentedArray:
    """Batched matmul over the segmented batch dim — no communication
    (paper Fig. 4 splits 12 square matrices across GPUs)."""
    plan = _binary_plan(
        "gemm_batched", a, b,
        lambda: jax.jit(lambda ad, bd: jnp.einsum("bij,bjk->bik", ad, bd)),
        cache)
    return a.with_data(plan(a.data, b.data))


def gemm_ksplit_schedule(a: SegmentedArray, b: SegmentedArray) -> str:
    """The reduction schedule ``gemm_ksplit`` picks for these operands:
    ``rs_ag`` (psum_scatter + all_gather, Rabenseifner-style — each
    device reduces 1/n of the product and the replicas are assembled by
    an all-gather, halving the bytes each link carries vs a plain psum)
    above ``comm.REDUCE_RS_AG_MIN_BYTES``, else ``psum``."""
    nseg = a.nseg
    out_rows = a.data.shape[0]
    nbytes = (out_rows * b.data.shape[1]
              * jnp.promote_types(a.dtype, b.dtype).itemsize)
    eligible = nseg > 1 and out_rows % nseg == 0
    if _comm.REDUCE_SCHEDULE is not None:
        return ("rs_ag" if _comm.REDUCE_SCHEDULE == "rs_ag" and eligible
                else "psum")
    if (eligible and not a.group.unified_memory
            and nbytes >= _comm.REDUCE_RS_AG_MIN_BYTES):
        return "rs_ag"
    return "psum"


def gemm_ksplit(a: SegmentedArray, b: SegmentedArray,
                cache: PlanCache | None = None) -> SegmentedArray:
    """A·B with the contraction dim segmented: local partial matmul +
    one inter-device reduction (the paper's non-scaling A·B case; on TPU
    the classic tensor-parallel matmul).  Large products decompose the
    reduction Rabenseifner-style — see :func:`gemm_ksplit_schedule`."""
    schedule = gemm_ksplit_schedule(a, b)

    def build():
        ax = _axis_arg(a.mesh_axes)

        def body(al, bl):
            part = al @ bl
            if schedule == "rs_ag":
                return _comm._psum_rs_ag(part, tuple(a.mesh_axes))
            return lax.psum(part, ax)

        sm = jax.shard_map(body, mesh=a.group.mesh,
                           in_specs=(P(None, ax), P(ax, None)),
                           out_specs=P(), check_vma=False)
        return jax.jit(sm)

    plan = _binary_plan("gemm_ksplit", a, b, build, cache,
                        extra=(schedule,))
    out = plan(a.data, b.data)
    return SegmentedArray(out, a.group, Policy.CLONE, 0, a.mesh_axes)

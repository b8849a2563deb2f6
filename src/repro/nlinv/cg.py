"""Conjugate gradient on the (rho, chat) pytree (inner solver of eq. 3).

lax.while_loop with max-iteration + relative-residual stopping, under
the device scope ``nlinv.cg``.  Two bodies share the loop scaffolding:

``cg``        the unfused baseline: every scalar product goes through
              ``dot`` (the distributed path passes the bound
              ``Communicator.vdot`` — the paper's 'scalar products of
              all data' CG entry in Table 1), and the vector updates are
              three separate ``uaxpy`` passes.

``cg_fused``  the hot path (2017 follow-up's kernel-fusion + overlap
              optimizations): the operator application returns
              ``<p, A p>`` fused into the channel-sum collective
              (``NlinvOps.normal_pap``), the ``x``/``r`` updates run as
              ONE pass with the ``r·r`` dot epilogue accumulated in the
              same kernel (``kernels.cg_fused``), and the search
              direction update is the fused ``p = r + beta*p`` step.
              Per iteration that is 2 collectives instead of 3 and one
              traversal of the iterate pytree instead of four; starting
              from ``x0 = 0`` also skips the initial operator
              application entirely (``A(0) = 0`` exactly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.cg_fused import ops as _fused_ops
from .operators import uaxpy, udot


@jax.named_scope("nlinv.cg")
def cg(A, rhs, x0, *, iters: int = 30, tol: float = 1e-6, dot=udot):
    """Solve A x = rhs, A SPD (normal operator + alpha I)."""
    r0 = uaxpy(-1.0, A(x0), rhs)
    p0 = r0
    rs0 = jnp.real(dot(r0, r0))
    thresh = tol * tol * rs0

    def cond(state):
        i, x, r, p, rs = state
        return jnp.logical_and(i < iters, rs > thresh)

    def body(state):
        i, x, r, p, rs = state
        Ap = A(p)
        alpha = rs / jnp.maximum(jnp.real(dot(p, Ap)), 1e-30)
        x = uaxpy(alpha, p, x)
        r = uaxpy(-alpha, Ap, r)
        rs_new = jnp.real(dot(r, r))
        beta = rs_new / jnp.maximum(rs, 1e-30)
        p = uaxpy(beta, p, r)
        return i + 1, x, r, p, rs_new

    _, x, _, _, _ = jax.lax.while_loop(cond, body, (0, x0, r0, p0, rs0))
    return x


def _tree_sum(parts):
    return sum(jax.tree.leaves(parts))


def _fused_update(alpha, p, ap, x, r, rs_sum):
    """Per-leaf single-pass updates; the per-leaf rs partials are merged
    by ``rs_sum`` (policy-aware on the distributed path)."""
    outs = jax.tree.map(
        lambda p_, ap_, x_, r_: _fused_ops.cg_update(alpha, p_, ap_, x_, r_),
        p, ap, x, r)
    x2 = jax.tree.map(lambda o: o[0], outs,
                      is_leaf=lambda o: isinstance(o, tuple))
    r2 = jax.tree.map(lambda o: o[1], outs,
                      is_leaf=lambda o: isinstance(o, tuple))
    parts = jax.tree.map(lambda o: o[2], outs,
                         is_leaf=lambda o: isinstance(o, tuple))
    return x2, r2, rs_sum(parts)


def _fused_xpby(r, p, beta):
    return jax.tree.map(
        lambda r_, p_: _fused_ops.xpby_dot(r_, p_, beta,
                                           with_dot=False)[0], r, p)


@jax.named_scope("nlinv.cg")
def cg_fused(apply_pap, rhs, *, iters: int = 30, tol: float = 1e-6,
             rs_sum=None, x0=None):
    """Fused-hot-path CG.

    ``apply_pap(p) -> (A p, <p, A p>)`` — the operator application with
    the curvature scalar fused into its own collective
    (``NlinvOps.normal_pap``).  ``rs_sum(partials_pytree) -> scalar``
    merges per-leaf ``sum |.|^2`` partials into the global residual norm
    (the ``Communicator.vdot`` policy reduction on the distributed path;
    default: plain sum — the single-program form).  ``x0=None`` starts
    at zero, for which ``r0 = rhs`` exactly (no operator application).
    """
    if rs_sum is None:
        rs_sum = _tree_sum
    if x0 is None:
        x = jax.tree.map(jnp.zeros_like, rhs)
        r0 = rhs
    else:
        x = x0
        ax0, _ = apply_pap(x0)
        r0 = uaxpy(-1.0, ax0, rhs)
    rs0 = rs_sum(jax.tree.map(
        lambda l: jnp.real(jnp.vdot(l, l)).astype(jnp.float32), r0))
    thresh = tol * tol * rs0

    def cond(state):
        i, x, r, p, rs = state
        return jnp.logical_and(i < iters, rs > thresh)

    def body(state):
        i, x, r, p, rs = state
        ap, pap = apply_pap(p)
        alpha = rs / jnp.maximum(jnp.real(pap), 1e-30)
        x, r, rs_new = _fused_update(alpha, p, ap, x, r, rs_sum)
        beta = rs_new / jnp.maximum(rs, 1e-30)
        p = _fused_xpby(r, p, beta)
        return i + 1, x, r, p, rs_new

    _, x, _, _, _ = jax.lax.while_loop(cond, body, (0, x, r0, r0, rs0))
    return x

"""Iteratively Regularized Gauss-Newton Method (paper eq. 3).

    (DG^H DG + alpha_n I)(x_{n+1} - x_n)
        = DG^H (y - G(x_n)) - alpha_n (x_n - x_ref)

with alpha_n = alpha0 * q^n and the previous frame as x_ref (temporal
regularization — the reason movie frames cannot be pipelined, §3.2).

The two cross-device reduction points are injected: ``channel_sum`` (the
Σ_j in DG^H) and ``dot`` (the CG scalar products).  The defaults are the
local single-program math; ``recon.Reconstructor`` passes its bound
``Communicator``'s verbs (``comm.allreduce_window`` / ``comm.vdot``),
which is the only way device communication ever enters this solver.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .cg import cg, cg_fused
from .operators import local_reducer, uaxpy, udot, uzeros


def irgnm(ops, y, x0, x_ref=None, *, newton: int = 7, cg_iters: int = 30,
          alpha0: float = 1.0, q: float = 1.0 / 3.0,
          channel_sum=None, dot=None):
    """Returns the solution pytree u = {rho, chat}."""
    if dot is None:
        dot = udot
    x = x0
    if x_ref is None:
        x_ref = x0   # pull toward the initial guess (rho=1, chat=0);
        # movies pass the (damped) previous frame instead — paper §3.2.
    alpha = jnp.asarray(alpha0, jnp.float32)
    for n in range(newton):
        with jax.named_scope("nlinv.newton"):
            r = uaxpy(-1.0, ops.G(x), y)                   # y - G(x)
            rhs = ops.DGH(x, r, channel_sum=channel_sum)
            rhs = uaxpy(alpha, uaxpy(-1.0, x, x_ref), rhs)  # - a (x - ref)
            A = lambda du: ops.normal(x, du, alpha, channel_sum=channel_sum)
            dx = cg(A, rhs, jax.tree.map(jnp.zeros_like, x),
                    iters=cg_iters, dot=dot)
            x = uaxpy(1.0, dx, x)
            alpha = alpha * q
    return x


def irgnm_fused(ops, y, x0, x_ref=None, *, newton: int = 7,
                cg_iters: int = 30, alpha0: float = 1.0, q: float = 1.0 / 3.0,
                reducer=None, rs_sum=None):
    """IRGNM on the fused hot path (same Newton/regularization schedule
    as :func:`irgnm`, same math, restructured per the 2017 follow-up):

    * the Newton-point constants (``c0``/conj planes) are precomputed
      once per linearization (``NlinvOps.precompute``) instead of
      re-derived inside every CG operator application;
    * the CG body runs the single-pass update kernels with the
      ``<p, A p>`` scalar fused into the channel-sum collective
      (``cg_fused`` + ``NlinvOps.normal_pap``) and starts from the exact
      ``r0 = rhs`` (``A(0) = 0``);
    * ``reducer`` is the fused DG^H reduction hook (windowed channel sum
      + scalar piggyback + overlapped dchat branch); ``rs_sum`` the
      policy-aware residual-norm reduction.  The defaults are the
      single-program degenerates, so this function is also the 1-device
      fast path.
    """
    if reducer is None:
        reducer = local_reducer
    x = x0
    if x_ref is None:
        x_ref = x0
    # DGH_fused skips the re-mask (premasked residuals); G_fused output
    # is masked by construction, so masking y ONCE here makes every
    # residual mask-supported for arbitrary caller data (a no-op when y
    # is already sampled k-space) — exactness, not an assumption.
    with jax.named_scope("nlinv.mask"):
        y = ops.sample(y)
    alpha = jnp.asarray(alpha0, jnp.float32)
    for n in range(newton):
        with jax.named_scope("nlinv.newton"):
            pre = ops.precompute(x)
            r = uaxpy(-1.0, ops.G_fused(x, c0=pre["c0"]), y)  # y - G(x)
            rhs, _ = ops.DGH_fused(pre, r, reducer=reducer)
            rhs = uaxpy(alpha, uaxpy(-1.0, x, x_ref), rhs)  # - a (x - ref)
            pap = lambda p: ops.normal_pap(pre, p, alpha, reducer=reducer)
            dx = cg_fused(pap, rhs, iters=cg_iters, rs_sum=rs_sum)
            x = uaxpy(1.0, dx, x)
            alpha = alpha * q
    return x


def postprocess(ops, u):
    """rho * |c| normalization: the displayed image (RSS-weighted), per
    slice when the unknowns carry a slice axis."""
    c = ops.coils(u["chat"])
    rss = jnp.sqrt(jnp.sum(jnp.abs(c) ** 2, axis=c.ndim - 3))
    return u["rho"] * rss

"""Reconstruction drivers, built entirely on the repro.core
Environment/Communicator layer (the paper's §3.2 decomposition as
policies and group-bound verbs, not specs).

Coil data ``y`` and the coil coefficients ``chat`` are NATURAL-segmented
across the communicator's group, the image ``rho`` and acquisition
geometry are CLONEd, the channel sum in DG^H is
``comm.allreduce_window`` (the paper's ``kern_all_red_p2p_2d``
4x-fewer-bytes trick when windowed to the centered FOV quarter), and the
CG scalar products are ``comm.vdot`` over the CLONE+NATURAL mixed
pytree.  ``Reconstructor`` is the one frame-solver API; a 1-device
``Communicator`` is the degenerate case — the same program with no-op
collectives.

``channel_sum`` strategy:

  full   all-reduce the whole doubled grid (paper-faithful baseline)
  crop   M_Omega zeroes everything outside the centered FOV quarter, so
         only that 2-D window is reduced and scattered back (the paper's
         kern_all_red_p2p_2d insight; 4x fewer bytes on the wire).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.env import Communicator, Environment
from ..core.runtime import DeviceGroup
from ..core.segmented import Policy
from ..kernels import registry as _kreg
from ..lib.plan import Plan, default_cache, group_token

# the kernel families the frame program traces through; their current
# block choices are part of the frame-plan identity
_KERNEL_FAMILIES = ("cg_fused", "coil_mult", "dft", "masked_allreduce")
from .irgnm import irgnm, irgnm_fused
from .operators import make_ops, sobolev_weight, uinit


def _coil_policy(dim: int):
    return Policy.NATURAL if dim == 0 else (Policy.NATURAL, dim)


def u_policies(coil_dim: int) -> dict:
    """Segmentation of the unknown pytree u = {rho, chat} (paper §3.2)
    with the coil axis at ``coil_dim`` of chat (and of y): 0 for one
    slice, one further in per leading axis (an SMS slice axis, a
    client-batch axis)."""
    return {"rho": Policy.CLONE, "chat": _coil_policy(coil_dim)}


def _as_communicator(comm, axis: str) -> Communicator:
    """Normalize comm=None | DeviceGroup | Communicator to a Communicator.

    A bare DeviceGroup is bound to ``axis`` (the coil-split axis), so
    multi-axis groups keep splitting coils over that one axis; an
    explicit Communicator carries its own mesh_axes and wins over
    ``axis``.
    """
    if comm is None:
        return Environment().subgroup(1, (axis,))
    if isinstance(comm, DeviceGroup):
        return Communicator(comm, (axis,))
    return comm


class Reconstructor:
    """One NLINV frame solver over a Communicator.

    The compiled function (``.fn``) maps
    ``(y, mask, fov, weight, x0, x_ref) -> (u, image)`` with ``y``/
    ``chat`` coil-segmented and everything else replicated.  ``__call__``
    forwards to it.  ``.fn_donate_carry`` is the same program with the
    Newton carry ``(x0, x_ref)`` buffers donated — the streaming engine's
    steady-state path.

    ``fused=True`` (default) runs the fused hot path (``irgnm_fused``:
    hoisted Newton-point constants, single-pass CG update kernels, the
    ``<p, Ap>`` scalar piggybacked on the channel-sum collective and the
    dchat FFT branch overlapped with it); ``fused=False`` is the unfused
    escape hatch with the original verb-per-op body.  ``overlap`` picks
    the fused reduction schedule: ``"psum"`` (one variadic all-reduce)
    or ``"p2p"`` (the chunked ``kern_all_red_p2p_2d`` ppermute ring with
    compute interleaved between transfer rounds).

    ``slices`` > 1 is SMS-NLINV (``operators.SmsOps``): every frame is M
    slices measured together, ``y`` (M, J, X, Y), ``mask`` (M, X, Y) (one
    per partition), ``rho`` (M, X, Y), ``chat`` (M, J, X, Y) and the
    image (M, X, Y); the coils stay split, now on dim 1.  ``slices=1``
    keeps the single-slice layout and program.
    """

    def __init__(self, comm: Communicator | DeviceGroup | None = None,
                 axis: str = "data", *, newton: int = 7, cg_iters: int = 30,
                 channel_sum: str = "crop", hierarchical: bool = False,
                 fused: bool = True, overlap: str = "psum",
                 slices: int = 1):
        if channel_sum not in ("full", "crop"):
            raise ValueError(f"channel_sum must be full|crop: {channel_sum}")
        if overlap not in ("psum", "p2p"):
            raise ValueError(f"overlap must be psum|p2p: {overlap}")
        self.comm = _as_communicator(comm, axis)
        self.axis = self.comm.axis
        self.newton, self.cg_iters = newton, cg_iters
        self.channel_sum, self.hierarchical = channel_sum, hierarchical
        self.fused, self.overlap = fused, overlap
        self.slices = int(slices)
        # the coil axis of y and chat: behind the slice axis, if any
        self.coil_dim = 1 if self.slices > 1 else 0
        self.plan_cache = default_cache()

    @property
    def group(self) -> DeviceGroup:
        return self.comm.group

    # -- the shard-local frame program (pure jnp + communicator verbs) ----
    def _frame_solve(self, y, mask, fov, weight, x0, x_ref):
        """Newton/CG stage only: acquisition -> solved ``u``.  The task
        pipeline (``repro.task``) runs this and ``_frame_image`` as
        separate graph nodes so the crop/readout of frame ``f-1`` and
        the solve of frame ``f`` are independently schedulable."""
        crop = self.channel_sum == "crop"

        ops = make_ops(mask, fov, weight)
        if self.fused:
            # Fused hot path: windowed channel sum + <p, Ap> piggyback +
            # overlapped dchat branch as ONE reducer hook, and the
            # residual-norm partials merged with the vdot policy rules
            # (rho CLONE counted once, chat NATURAL psum'd).
            def reducer(prod, extras, compute):
                g = prod.shape[-1]
                q = g // 4
                win = ((q, 3 * q), (q, 3 * q)) if crop else None
                return self.comm.allreduce_overlap(
                    prod, win, axis=self.axis, extras=extras,
                    compute=compute, p2p=self.overlap == "p2p",
                    hierarchical=self.hierarchical)

            def rs_sum(parts):
                nat = self.comm.allreduce(parts["chat"], axis=self.axis)
                return parts["rho"] + nat
            u = irgnm_fused(ops, y, x0, x_ref, newton=self.newton,
                            cg_iters=self.cg_iters, reducer=reducer,
                            rs_sum=rs_sum)
        else:
            def csum(prod):
                g = prod.shape[-1]
                q = g // 4
                win = ((q, 3 * q), (q, 3 * q)) if crop else None
                return self.comm.allreduce_window(
                    prod, win, axis=self.axis, reduce_dim=self.coil_dim,
                    hierarchical=self.hierarchical)

            def dot(a, b):
                return self.comm.vdot(a, b, axis=self.axis,
                                      policies=u_policies(self.coil_dim))

            u = irgnm(ops, y, x0, x_ref, newton=self.newton,
                      cg_iters=self.cg_iters, channel_sum=csum, dot=dot)
        return u

    @jax.named_scope("nlinv.image")
    def _frame_image(self, mask, fov, weight, u):
        """Crop/readout stage: solved ``u`` -> displayed image (the
        root-sum-of-squares channel combination, per slice)."""
        ops = make_ops(mask, fov, weight)
        c = ops.coils(u["chat"])
        rss = self.comm.allreduce_window(jnp.abs(c) ** 2, None,
                                         axis=self.axis,
                                         reduce_dim=self.coil_dim)
        return u["rho"] * jnp.sqrt(rss)

    def _frame(self, y, mask, fov, weight, x0, x_ref):
        u = self._frame_solve(y, mask, fov, weight, x0, x_ref)
        return u, self._frame_image(mask, fov, weight, u)

    def _policies(self, batched: bool = False):
        """(y policy, u policies) of the frame program; a client-batch
        dim moves the coil split one dim further in."""
        d = self.coil_dim + int(batched)
        return _coil_policy(d), u_policies(d)

    def _build(self, donate: bool):
        clone = Policy.CLONE
        ypol, upol = self._policies()
        in_pol = (ypol, clone, clone, clone, upol, upol)
        return self.comm.spmd(self._frame,
                              in_policies=in_pol,
                              out_policies=(upol, clone),
                              check_vma=False,
                              donate_argnums=(4, 5) if donate else ())

    # -- the batched frame program (serving layer: B clients, one launch) -
    def _frame_batched(self, y, mask, fov, weight, x0, x_ref):
        """B independent frame solves in one SPMD program over a leading
        client-batch dim (``fov``/``weight`` shared by every row).  The
        rows run one after another through the unbatched frame body, and
        each row's result is written back into the carry stack it was
        read from, so a donated ``x0`` holds the output and the solve's
        working memory is one row's at any width."""
        def row(i, t):
            return jax.tree.map(lambda a: a[i], t)

        def solve_row(i, carry):
            u, img = carry
            ui, img_i = self._frame(y[i], mask[i], fov, weight,
                                    row(i, u), row(i, x_ref))
            u = jax.tree.map(lambda s, r: s.at[i].set(r), u, ui)
            return u, img.at[i].set(img_i)

        return jax.lax.fori_loop(0, y.shape[0], solve_row,
                                 (x0, jnp.zeros_like(x0["rho"])))

    def _build_batched(self, donate: bool):
        clone = Policy.CLONE
        ypol, upol = self._policies(batched=True)
        in_pol = (ypol, clone, clone, clone, upol, upol)
        return self.comm.spmd(self._frame_batched,
                              in_policies=in_pol,
                              out_policies=(upol, clone),
                              check_vma=False,
                              donate_argnums=(4, 5) if donate else ())

    def _plan_batched(self, width: int, donate: bool):
        """Batched plans key on the batch WIDTH: the scheduler buckets
        widths to a small set, and every bucket's compile shows up as
        one visible plan build (never a silent recompile)."""
        key = ("nlinv", "frame_batched", group_token(self.comm), int(width),
               self.newton, self.cg_iters, self.channel_sum,
               self.hierarchical, self.fused, self.overlap, self.slices,
               bool(donate), _kreg.choices_token(_KERNEL_FAMILIES))
        return self.plan_cache.get_or_build(
            key, lambda: Plan(key=key, fn=self._build_batched(donate),
                              lib="nlinv", op="frame_batched"))

    def _plan(self, donate: bool):
        """The frame program as a library plan: keyed on the solver
        configuration + group so the streaming engine's steady state is
        pure cache hits (and the hit/miss counters prove it)."""
        key = ("nlinv", "frame", group_token(self.comm), self.newton,
               self.cg_iters, self.channel_sum, self.hierarchical,
               self.fused, self.overlap, self.slices, bool(donate),
               _kreg.choices_token(_KERNEL_FAMILIES))
        return self.plan_cache.get_or_build(
            key, lambda: Plan(key=key, fn=self._build(donate),
                              lib="nlinv", op="frame"))

    # -- staged plans (the task-graph pipeline's nodes) -------------------
    def _build_solve(self, donate: bool):
        clone = Policy.CLONE
        ypol, upol = self._policies()
        in_pol = (ypol, clone, clone, clone, upol, upol)
        return self.comm.spmd(self._frame_solve, in_policies=in_pol,
                              out_policies=upol, check_vma=False,
                              donate_argnums=(4, 5) if donate else ())

    def _build_image(self):
        clone = Policy.CLONE
        return self.comm.spmd(self._frame_image,
                              in_policies=(clone, clone, clone,
                                           self._policies()[1]),
                              out_policies=clone, check_vma=False)

    def _plan_stage(self, stage: str, builder):
        key = ("nlinv", stage, group_token(self.comm), self.newton,
               self.cg_iters, self.channel_sum, self.hierarchical,
               self.fused, self.overlap, self.slices,
               _kreg.choices_token(_KERNEL_FAMILIES))
        return self.plan_cache.get_or_build(
            key, lambda: Plan(key=key, fn=builder(), lib="nlinv",
                              op=stage))

    @property
    def fn_solve(self):
        """Newton/CG stage of the frame program (``u`` only) — the
        ``solve`` node of the task-graph pipeline.  Not donated: with
        several frames in flight the carry of frame ``f-1`` is still a
        live input of ``damp`` when frame ``f`` dispatches."""
        return self._plan_stage("frame_solve",
                                lambda: self._build_solve(False)).fn

    @property
    def fn_image(self):
        """Crop/readout stage ``(mask, fov, weight, u) -> image`` — the
        ``crop`` node of the task-graph pipeline."""
        return self._plan_stage("frame_image", self._build_image).fn

    @property
    def fn(self):
        return self._plan(donate=False).fn

    @property
    def fn_donate_carry(self):
        return self._plan(donate=True).fn

    def fn_batched(self, width: int, *, donate: bool = False):
        """The B-client frame program for batch width ``width``:
        ``(y (B,J,X,Y), mask (B,X,Y), fov, weight, u (B,...), x_ref
        (B,...)) -> (u, images (B,X,Y))`` (with an SMS slice axis behind
        B).  Plan-cached per width."""
        return self._plan_batched(width, donate).fn

    def __call__(self, y, mask, fov, weight, x0, x_ref):
        return self.fn(y, mask, fov, weight, x0, x_ref)

    # -- carry/constant placement through the verbs -----------------------
    def init_carry(self, ncoils: int, grid: int):
        """Device-placed Newton carry (rho=1 CLONE, chat=0 NATURAL), with
        the reconstructor's ``slices``."""
        u = uinit(ncoils, grid, slices=self.slices)
        return {"rho": self.comm.bcast(u["rho"]).data,
                "chat": self.put_frame(u["chat"])}

    def put_frame(self, y):
        """Segment one frame of coil data onto the group (on the coil
        dim, ``coil_dim``)."""
        return self.comm.container(y, dim=self.coil_dim).data

    def put_const(self, x):
        """Replicate a per-frame constant (mask/fov/weight)."""
        return self.comm.bcast(x).data


@functools.lru_cache(maxsize=None)
def _single_device_reconstructor(newton: int, cg_iters: int) -> Reconstructor:
    # "full" channel sum: bit-identical to the classic unsegmented solver.
    return Reconstructor(newton=newton, cg_iters=cg_iters,
                         channel_sum="full")


def reconstruct_frame(y, mask, fov, weight, x0, x_ref, *,
                      newton=7, cg_iters=30):
    """Single-device NLINV for one frame — the degenerate Reconstructor.
    y: (J, X, Y)."""
    rec = _single_device_reconstructor(newton, cg_iters)
    return rec(y, mask, fov, weight, x0, x_ref)


def make_dist_reconstruct(comm, axis: str = "data", *,
                          newton=7, cg_iters=30, channel_sum="crop",
                          fused=True):
    """Compiled distributed NLINV: coils split over ``axis`` (paper §3.2).
    ``comm`` may be a Communicator or a DeviceGroup.  Returns the jitted
    frame function (kept for callers that want the bare callable; new
    code should hold the ``Reconstructor``)."""
    return Reconstructor(comm, axis, newton=newton, cg_iters=cg_iters,
                         channel_sum=channel_sum, fused=fused).fn


def pad_channels(y, nseg, axis: int = 0):
    """Zero-pad the coil dim to a multiple of the group size (zero
    channels are exact no-ops for all NLINV sums)."""
    J = y.shape[axis]
    Jp = -(-J // nseg) * nseg
    if Jp == J:
        return y
    pad = np.zeros(y.shape[:axis] + (Jp - J,) + y.shape[axis + 1:], y.dtype)
    return np.concatenate([y, pad], axis=axis)


def reconstruct_movie(data, *, newton=7, cg_iters=30, damping=0.9,
                      frame_fn=None):
    """Blocking sequential movie loop (frames depend on x_ref: no frame
    parallelism, paper §3.2).  Returns (F, X, Y) images.  This is the
    latency baseline; ``repro.nlinv.stream.FrameStream`` is the
    transfer-overlapped real-time engine.
    """
    y, masks, fov = data["y"], data["masks"], data["fov"]
    F, J, g, _ = y.shape
    weight = sobolev_weight(g)
    u = uinit(J, g)
    x_ref = u
    images = []
    for f in range(F):
        if frame_fn is None:
            u, img = reconstruct_frame(
                jnp.asarray(y[f]), jnp.asarray(masks[f]), jnp.asarray(fov),
                jnp.asarray(weight), u, x_ref,
                newton=newton, cg_iters=cg_iters)
        else:
            u, img = frame_fn(y[f], masks[f], fov, weight, u, x_ref)
        x_ref = jax.tree.map(lambda a: damping * a, u)
        images.append(img)
    return jnp.stack(images)

"""NLINV operators (paper §3.1, eq. 2-3).

    F = P_k . DTFT . M_Omega . C . W^{-1}

Unknowns u = (rho, c_hat_j): image + coil coefficients in the weighted
Fourier domain; c_j = W(c_hat_j) = IFFT(w . c_hat_j) with the Sobolev
weight w(k) = (1 + s|k|^2)^{-l} encoding coil smoothness.

The operator count per application matches the paper's Table 1:
  G   (=F):   2 FFT-batches, 4 pointwise, 1 dot with mask
  DG:         2 FFT-batches, 5 pointwise
  DG^H:       2 FFT-batches, 4 pointwise, 1 channel-sum, 1 all-reduce

All functions are pure jnp on (J, X, Y) coil stacks, jit/shard_map-safe;
the distributed path segments J across devices (paper's decomposition)
and the channel-sum in DG^H becomes the block-wise all-reduce.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.coil_mult import (coil_adjoint, coil_forward, coil_lincomb,
                                 plane_mult, sms_adjoint, sms_lincomb)
from ..lib.blas import tree_axpy, tree_vdot
from ..lib.fft import fft2 as _cfft2


def sobolev_weight(grid: int, s: float = 32.0, l: int = 4) -> np.ndarray:
    """w(k) = (1 + s |k|^2)^{-l/2} on the centered grid (Uecker 2008)."""
    k = np.fft.fftshift(np.fft.fftfreq(grid))  # centered, cycles/sample
    ky, kx = np.meshgrid(k, k, indexing="ij")
    k2 = (kx ** 2 + ky ** 2) * 4.0             # normalize to ~[-1,1]^2
    return ((1.0 + s * k2) ** (-l / 2.0)).astype(np.float32)


def fft2c(x):
    return _cfft2(x, inverse=False, centered=True)


def ifft2c(x):
    return _cfft2(x, inverse=True, centered=True)


@dataclasses.dataclass(frozen=True)
class NlinvOps:
    """Closure over the acquisition geometry of one frame."""
    mask: jnp.ndarray      # (X, Y) P_k sampling mask (float 0/1)
    fov: jnp.ndarray       # (X, Y) M_Omega
    weight: jnp.ndarray    # (X, Y) Sobolev w

    # -- variable transform ------------------------------------------------
    def coils(self, chat):
        """c_j = W(c_hat_j): weighted k-space -> smooth image coils."""
        return ifft2c(chat * self.weight)

    def coils_adj(self, c):
        """W^H."""
        return fft2c(c) * self.weight

    def sample(self, k):
        """P_k: the sampling mask applied to a k-space coil stack."""
        return plane_mult(k, self.mask)

    # -- forward model -----------------------------------------------------
    def G(self, u):
        """u = {rho (X,Y), chat (J,X,Y)} -> sampled k-space (J,X,Y)."""
        c = self.coils(u["chat"])
        img = self.fov * (u["rho"][None] * c)
        return self.mask[None] * fft2c(img)

    def DG(self, u0, du):
        """Directional derivative at u0."""
        c0 = self.coils(u0["chat"])
        dc = self.coils(du["chat"])
        img = self.fov * (du["rho"][None] * c0 + u0["rho"][None] * dc)
        return self.mask[None] * fft2c(img)

    def DGH(self, u0, r, *, channel_sum=None):
        """Adjoint of DG applied to residual r (J,X,Y).

        ``channel_sum``: override for the Sum_j reduction — the
        distributed path passes the all-reduce of the paper's P2P kernel.
        """
        c0 = self.coils(u0["chat"])
        z = self.fov[None] * ifft2c(self.mask[None] * r)
        prod = jnp.conj(c0) * z
        if channel_sum is None:
            drho = jnp.sum(prod, axis=0)
        else:
            drho = channel_sum(prod)
        dchat = self.coils_adj(jnp.conj(u0["rho"])[None] * z)
        return {"rho": drho, "chat": dchat}

    def normal(self, u0, du, alpha, *, channel_sum=None):
        """(DG^H DG + alpha I) du — the CG system matrix (eq. 3 LHS)."""
        out = self.DGH(u0, self.DG(u0, du), channel_sum=channel_sum)
        return {"rho": out["rho"] + alpha * du["rho"],
                "chat": out["chat"] + alpha * du["chat"]}

    # -- fused hot path (2017 follow-up: kernel fusion + comm overlap) -----
    #
    # Same math as G/DG/DGH, restructured for the per-frame latency
    # budget: the Newton-point constants (c0 = W(chat0), conj planes) are
    # precomputed ONCE per linearization instead of re-derived inside
    # every CG iteration; the pointwise chains run through the
    # generalized ``coil_mult`` kernel family instead of materializing
    # intermediates; and the DG^H channel reduction is a fused collective
    # (scalar piggyback + overlapped dchat branch) injected by the
    # caller.  Exactness notes: the forward/derivative outputs are
    # supported on ``mask`` (0/1), so DG^H inside the normal operator may
    # skip the re-mask (mask^2 = mask); A(0) = 0 exactly, so CG may start
    # from r0 = rhs without applying the operator.

    def precompute(self, u0):
        """Per-Newton-point constants hoisted out of the CG loop (the
        paper's Table 1 assumes c0 is precomputed; the unfused methods
        re-derive it per operator application)."""
        return {"rho0": u0["rho"], "rho0c": jnp.conj(u0["rho"]),
                "c0": self.coils(u0["chat"])}

    def G_fused(self, u, c0=None):
        """Forward model through the fused pointwise chain."""
        c = self.coils(u["chat"]) if c0 is None else c0
        img = coil_lincomb(u["rho"], c, scale=self.fov)
        return plane_mult(fft2c(img), self.mask)

    def DG_fused(self, pre, du):
        """Derivative at the precomputed Newton point ``pre``."""
        dc = self.coils(du["chat"])
        img = coil_lincomb(du["rho"], pre["c0"], pre["rho0"], dc,
                           scale=self.fov)
        return plane_mult(fft2c(img), self.mask)

    def DGH_fused(self, pre, r, *, reducer, extras=(), premasked=True):
        """Adjoint of DG with the fused reduction schedule.

        ``reducer(prod, extras, compute)`` performs the cross-device
        channel sum of the locally channel-summed ``prod`` (windowed on
        the distributed path), reduces ``extras`` in the same collective
        and overlaps the independent ``compute`` branch (the dchat FFT
        chain) with the transfer; it returns
        ``(drho, extras_out, dchat)``.  ``premasked=True`` asserts ``r``
        is mask-supported (true for residuals and DG outputs) and skips
        the re-mask.  Returns ``({rho, chat}, extras_out)``.
        """
        rin = r if premasked else plane_mult(r, self.mask)
        z = plane_mult(ifft2c(rin), self.fov)
        prod = coil_adjoint(pre["c0"], z)            # local Sum_j conj(c0)*z

        def dchat():
            return plane_mult(fft2c(coil_forward(z, pre["rho0c"])),
                              self.weight)

        drho, extras_out, dchat_out = reducer(prod, tuple(extras), dchat)
        return {"rho": drho, "chat": dchat_out}, extras_out

    def normal_pap(self, pre, du, alpha, *, reducer):
        """Fused normal operator application returning BOTH ``A du`` and
        the CG curvature scalar ``<du, A du>`` for one extra collective
        of zero: by self-adjointness

            <du, (DG^H DG + alpha I) du> = ||DG du||^2 + alpha ||du||^2,

        so the scalar needs only local partials — the segmented part
        rides the channel-sum collective via ``extras`` (paper Table 1's
        'scalar products of all data' without its own all-reduce).
        Returns ``(A du, pap)``.
        """
        dgp = self.DG_fused(pre, du)
        nat = (jnp.real(jnp.vdot(dgp, dgp)) +
               alpha * jnp.real(jnp.vdot(du["chat"], du["chat"])))
        clone = alpha * jnp.real(jnp.vdot(du["rho"], du["rho"]))
        out, (nat_red,) = self.DGH_fused(pre, dgp, reducer=reducer,
                                         extras=(nat,))
        pap = nat_red + clone
        ap = {"rho": out["rho"] + alpha * du["rho"],
              "chat": out["chat"] + alpha * du["chat"]}
        return ap, pap


def sms_phase(slices: int) -> np.ndarray:
    """Xi_qm = exp(2 pi i q m / M): the RF phase cycle of simultaneous
    multi-slice excitation, a Fourier encoding along the slice axis
    (Rosenzweig et al., SMS-NLINV, MRM 2018).  A host constant."""
    q = np.arange(slices)
    return np.exp(2j * np.pi * np.outer(q, q) / slices).astype(np.complex64)


@dataclasses.dataclass(frozen=True)
class SmsOps(NlinvOps):
    """SMS-NLINV: M slices measured together, partition q with its own
    mask P_q and the slice mixing Xi (``sms_phase``):

        y_qj = P_q F( fov * Sum_m Xi_qm rho_m c_mj )

    Unknowns rho (M, X, Y), chat (M, J, X, Y); masks (Q=M, X, Y).  In
    DG^H every slice receives every partition's residual,
    z_mj = Sum_q conj(Xi_qm) fov IFFT(P_q r_qj), so the normal operator
    couples the slices.  The mixing runs inside the fused coil kernels
    (``sms_lincomb``, ``sms_adjoint``) under the device scope
    ``nlinv.sms``; ``precompute`` and ``normal_pap`` are the single-slice
    ones, which act per leading index."""
    mix: np.ndarray = None       # (Q, M) complex Xi

    # The 2-D FFTs run one slice at a time (``lax.map``): a slice's (J, X,
    # Y) transform keeps its intermediates at the single-slice program's
    # size, which the compiler holds on chip; a (M, J, X, Y) one spills.
    @staticmethod
    def _per_slice(f, *xs):
        return jax.lax.map(lambda a: f(*a), xs)

    def coils(self, chat):
        return self._per_slice(lambda c: ifft2c(c * self.weight), chat)

    def coils_adj(self, c):
        return self._per_slice(lambda x: fft2c(x) * self.weight, c)

    def _sampled_fft(self, img):
        """P_q FFT(img_q), partition by partition."""
        return self._per_slice(lambda x, m: fft2c(x) * m, img, self.mask)

    def sample(self, k):
        return k * self.mask[:, None]

    def _mixed(self, a, x, b=None, y=None):
        with jax.named_scope("nlinv.sms"):
            return sms_lincomb(a, x, b, y, scale=self.fov, mix=self.mix)

    def _unmixed(self, v, c0, rho0c):
        with jax.named_scope("nlinv.sms"):
            return sms_adjoint(v, c0, self.fov, rho0c, mix=self.mix)

    # -- plain operators (the unfused path: no kernels) --------------------
    def _xi(self, t, adjoint=False):
        """Sum_m Xi_qm t_m, or with ``adjoint`` Sum_q conj(Xi_qm) t_q."""
        mix = np.conj(self.mix).T if adjoint else self.mix
        return jnp.stack([sum(complex(mix[a, b]) * t[b]
                              for b in range(mix.shape[1]))
                          for a in range(mix.shape[0])])

    def G(self, u):
        img = self.fov * self._xi(u["rho"][:, None] * self.coils(u["chat"]))
        return self.sample(fft2c(img))

    def DG(self, u0, du):
        img = (du["rho"][:, None] * self.coils(u0["chat"])
               + u0["rho"][:, None] * self.coils(du["chat"]))
        return self.sample(fft2c(self.fov * self._xi(img)))

    def DGH(self, u0, r, *, channel_sum=None):
        z = self.fov * self._xi(ifft2c(self.sample(r)), adjoint=True)
        prod = jnp.conj(self.coils(u0["chat"])) * z
        drho = jnp.sum(prod, axis=1) if channel_sum is None \
            else channel_sum(prod)
        return {"rho": drho,
                "chat": self.coils_adj(jnp.conj(u0["rho"])[:, None] * z)}

    # -- fused hot path ----------------------------------------------------
    def G_fused(self, u, c0=None):
        c = self.coils(u["chat"]) if c0 is None else c0
        return self._sampled_fft(self._mixed(u["rho"], c))

    def DG_fused(self, pre, du):
        return self._sampled_fft(self._mixed(
            du["rho"], pre["c0"], pre["rho0"], self.coils(du["chat"])))

    def DGH_fused(self, pre, r, *, reducer, extras=(), premasked=True):
        rin = r if premasked else self.sample(r)
        # channel sum and conj(rho0) z in one pass; z is never written
        prod, dz = self._unmixed(self._per_slice(ifft2c, rin), pre["c0"],
                                 pre["rho0c"])

        def dchat():
            return self.coils_adj(dz)

        drho, extras_out, dchat_out = reducer(prod, tuple(extras), dchat)
        return {"rho": drho, "chat": dchat_out}, extras_out


def make_ops(mask, fov, weight) -> NlinvOps:
    """The operators of one frame's geometry: a (X, Y) mask is the
    single-slice model, a (M, X, Y) stack of partition masks SMS-NLINV
    with M slices."""
    mask = jnp.asarray(mask, jnp.float32)
    fov = jnp.asarray(fov, jnp.float32)
    weight = jnp.asarray(weight, jnp.float32)
    if mask.ndim == 3:
        return SmsOps(mask, fov, weight, sms_phase(mask.shape[0]))
    return NlinvOps(mask, fov, weight)


# -- pytree algebra for (rho, chat) ----------------------------------------

def _lead(slices: int) -> tuple:
    """The leading slice axis of the unknowns: none for one slice."""
    return (slices,) if slices > 1 else ()


def uzeros(J, grid, dtype=jnp.complex64, slices: int = 1):
    s = _lead(slices)
    return {"rho": jnp.zeros(s + (grid, grid), dtype),
            "chat": jnp.zeros(s + (J, grid, grid), dtype)}


def uinit(J, grid, dtype=jnp.complex64, slices: int = 1):
    """Paper/Uecker init: rho = 1, chat = 0 (per slice)."""
    s = _lead(slices)
    return {"rho": jnp.ones(s + (grid, grid), dtype),
            "chat": jnp.zeros(s + (J, grid, grid), dtype)}


def uaxpy(a, x, y):
    """a*x + y — routed through ``repro.lib.blas.tree_axpy`` so the
    single-device and distributed paths share one implementation."""
    return tree_axpy(a, x, y)


def udot(x, y):
    """<x, y> with conjugation, summed over both components (real part
    is what CG uses; kept complex for adjointness tests).  Routed
    through ``repro.lib.blas.tree_vdot``."""
    return tree_vdot(x, y)


def local_reducer(prod, extras, compute):
    """The single-program degenerate of the fused DG^H reduction hook:
    no collective, the overlapped branch just runs."""
    return prod, tuple(extras), compute() if compute is not None else None

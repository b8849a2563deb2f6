"""The two production workloads behind ``StreamScheduler``.

:class:`NlinvStreamWorkload` — N concurrent real-time NLINV streams.
Independent clients' Newton solves are stacked on a leading batch dim of
the ``(rho, chat)`` carry pytree and solved in ONE SPMD launch per tick
(``Reconstructor.fn_batched``), whose rows run one after another
through the unbatched frame body: batching saves the per-launch host
work of B programs, and each row costs what the unbatched frame costs.
Two invariants keep the tick cheap:

  * the stacked carry is PERSISTENT — while the ready set is stable
    (the steady state of K clients streaming) the carry never leaves
    the device or gets restacked; it is sliced back into per-session
    state only when the membership changes (client joins/leaves/skips
    a tick: the "mixed frame phases" case);
  * uploads happen at submit() time through the same
    ``upload_frame`` helper the single-stream ``FrameStream`` uses, so
    every client's next acquisition lands behind the in-flight tick.

:class:`LMDecodeWorkload` — greedy continuous-batching LM decode, the
old bespoke ``Engine`` loop re-expressed as a Workload: admission =
prefill into a KV slot from the explicit :class:`SlotPool`, one tick =
one decode step per active request, close = slot free.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.runtime import span
from ..ft.remesh import migrate_carry, pad_rows
from ..nlinv.operators import sobolev_weight
from ..nlinv.recon import Reconstructor, pad_channels
from ..nlinv.stream import damper, upload_frame
from ..task import Executor, TaskGraph
from .scheduler import Rejected, Session, Workload


def stack_carries(carries: list) -> dict:
    """Stack per-session ``(rho, chat)`` carries on a new leading batch
    dim (one jnp.stack per leaf)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *carries)


def unstack_carry(stacked, i: int):
    """Slice session ``i``'s carry back out of the stacked pytree."""
    return jax.tree.map(lambda a: a[i], stacked)


class NlinvStreamWorkload(Workload):
    """B NLINV frame solves per tick, one batched SPMD launch.

    Work item (per ``submit``): a ``(y, mask)`` acquisition with ``y``
    of shape (J, X, Y) (channel-padded here) and ``mask`` (X, Y); for a
    reconstructor with ``slices`` M > 1 (SMS-NLINV) ``y`` (M, J, X, Y)
    and one mask per partition, (M, X, Y).
    Result: the reconstructed (X, Y) or (M, X, Y) image (device array,
    ready) — or a
    :class:`~repro.serve.Rejected` status when the health check finds a
    non-finite output (the client is quarantined: its carry row is
    re-initialized in place, every other row is untouched).
    Geometry (grid, coil count, ``meta["slices"]``, default 1) is fixed
    per workload — one scanner protocol per scheduler; the first session
    pins it, and its slices must be the reconstructor's.

    ``retry`` (a ``repro.ft.RestartPolicy``) arms the tick executor's
    transient-task retry; ``operating_points`` is the degradation
    ladder — ``((newton, cg_iters), ...)`` below nominal, coarsest
    last (default: one derived point at roughly half the CG work).
    Newton/CG depth is part of every batched plan key, so each point
    compiles its own program and switching is just a cache lookup after
    the first visit.
    """

    def __init__(self, rec: Reconstructor, *, damping: float = 0.9,
                 retry=None, operating_points=None):
        self.rec = rec
        self.damping = damping
        self._exec = Executor(retry=retry)
        self._damp = damper(damping)
        self._geom = None            # (J_padded, grid), pinned by 1st open
        self._fov_d = self._w_d = None
        # persistent stacked carry: (sids tuple, u_stack, x_ref_stack),
        # plus the Session objects whose carries live in that stack
        self._stack = None
        self._by_sid: dict = {}
        # -- fault tolerance ----------------------------------------------
        if operating_points is None:
            n0, c0 = rec.newton, rec.cg_iters
            pt = (max(n0 - 1, 1), max(c0 // 2, 2))
            operating_points = () if pt == (n0, c0) else (pt,)
        self._points = ((rec.newton, rec.cg_iters),) \
            + tuple(operating_points)
        self._level = 0
        self._health_jit = None
        self.quarantined = 0         # total quarantine events
        self.remeshes = 0            # survivor-group migrations
        # stack churn: 0 in the steady state of a stable ready set
        self.restacks = 0            # carry stacks rebuilt
        self.spills = 0              # stacks written back to sessions

    # -- degradation ladder (scheduler deadline enforcement) --------------
    @property
    def levels(self) -> int:
        return len(self._points) - 1

    def set_level(self, level: int) -> None:
        """Switch the Newton/CG operating point (0 = nominal).  The
        carry shapes are level-independent, so the persistent stack
        stays put; only the plan key changes."""
        if not 0 <= level <= self.levels:
            raise ValueError(f"level {level} outside 0..{self.levels}")
        if level == self._level:
            return
        self._level = level
        self.rec.newton, self.rec.cg_iters = self._points[level]

    def counters(self) -> dict:
        return {"slices": self.rec.slices,
                "retried_tasks": self._exec.retried,
                "quarantined": self.quarantined,
                "restacks": self.restacks,
                "spills": self.spills,
                "remeshes": self.remeshes}

    # -- session lifecycle ------------------------------------------------
    def open_session(self, session: Session):
        g = int(session.meta["grid"])
        slices = int(session.meta.get("slices", 1))
        if slices != self.rec.slices:
            raise ValueError(
                f"session slices={slices} do not match the "
                f"reconstructor's {self.rec.slices}: one protocol per "
                f"scheduler")
        J = pad_channels(np.zeros((int(session.meta["ncoils"]), 1, 1),
                                  np.complex64),
                         self.rec.comm.size).shape[0]
        if self._geom is None:
            self._geom = (J, g)
            self._fov_d = self.rec.put_const(
                np.asarray(session.meta["fov"]))
            self._w_d = self.rec.put_const(
                np.asarray(session.meta.get("weight",
                                            sobolev_weight(g))))
        elif self._geom != (J, g):
            raise ValueError(
                f"session geometry (J={J}, grid={g}) does not match the "
                f"workload's {self._geom}: one protocol per scheduler")
        u = self.rec.init_carry(J, g)
        # x_ref starts equal to u but must be a distinct buffer
        return {"u": u, "x_ref": jax.tree.map(lambda a: a + 0, u)}

    def enqueue(self, session: Session, item):
        """Upload at submit time: the scatter/bcast of this frame lands
        while the current tick's solve is still in flight (the serving
        analogue of FrameStream's double buffer)."""
        y, mask = item
        ax = self.rec.coil_dim
        y = pad_channels(np.asarray(y), self.rec.comm.size, axis=ax)
        if self._geom is not None and y.shape[ax] < self._geom[0]:
            # after an elastic remesh the pinned coil dim can exceed the
            # raw padding (J was padded for the OLD group size); zero
            # channels are exact NLINV no-ops, so top up
            y = np.moveaxis(pad_rows(np.moveaxis(y, ax, 0),
                                     self._geom[0]), 0, ax)
        return upload_frame(self.rec, y, mask)

    def close_session(self, session: Session) -> None:
        self._spill(keep=lambda sid: sid != session.sid)

    # -- the batched tick -------------------------------------------------
    def _spill(self, keep=lambda sid: True) -> None:
        """Write the stacked carry back into per-session state (dropping
        sessions ``keep`` rejects) and forget the stack."""
        if self._stack is None:
            return
        sids, ub, xb = self._stack
        self._stack = None
        self.spills += 1
        for i, sid in enumerate(sids):
            s = self._by_sid.get(sid)
            if s is None or not keep(sid):
                continue
            s.state["u"] = unstack_carry(ub, i)
            s.state["x_ref"] = unstack_carry(xb, i)

    def step(self, batch: list, width: int) -> list:
        sessions = [s for s, _ in batch]
        sids = tuple(s.sid for s in sessions)
        B = len(batch)
        if self._stack is not None and self._stack[0][:B] == sids \
                and len(self._stack[0]) == width:
            # steady state: same members, same width — reuse in place
            _, ub, xb = self._stack
        else:
            # membership or width changed: write everyone's carry back
            # to their session BEFORE the new map is installed
            with span("serve.restack", width=width):
                self._spill()
                self.restacks += 1
                # pad the launch to the bucket width by replicating the
                # last session's row (rows are independent; padded rows
                # are computed and discarded)
                rows = sessions + [sessions[-1]] * (width - B)
                ub = stack_carries([s.state["u"] for s in rows])
                xb = stack_carries([s.state["x_ref"] for s in rows])
        pads = [item for _, item in batch]
        pads += [pads[-1]] * (width - B)
        # One tick is one task graph: the stack of the already-uploaded
        # acquisitions is an explicit copy edge into the batched solve,
        # and the fence happens once, at the executor's sinks, instead
        # of an ad-hoc block on the image batch.
        g = TaskGraph()
        g.copy("stack",
               lambda: (jnp.stack([yd for yd, _ in pads]),
                        jnp.stack([md for _, md in pads])),
               outputs=("yb", "mb"))
        # the stacked carry is replaced every tick, so its two largest
        # buffers are donated to the launch (as in FrameStream)
        g.add("solve", self.rec.fn_batched(width, donate=True),
              inputs=("yb", "mb", "fov", "weight", "u_prev", "xref_prev"),
              outputs=("u", "img"), group=self.rec.comm)
        g.add("damp", self._damp, inputs=("u",), outputs=("xref",),
              group=self.rec.comm)
        vals = self._exec.run(
            g, feeds={"fov": self._fov_d, "weight": self._w_d,
                      "u_prev": ub, "xref_prev": xb},
            outputs=("u", "xref", "img", "yb"))
        ub, xb, imgb = vals["u"], vals["xref"], vals["img"]
        # fused health check: one jitted all-finite reduction over the
        # carry + image + acquisition rows, one (width,) bool vector to
        # the host.  The INPUT rows matter: a NaN acquisition makes the
        # CG residual norm NaN, its `rs > thresh` guard False — the
        # solve degenerates to du = 0 and would silently deliver a
        # stale image; the only honest outcome is a Rejected frame.
        with span("serve.health"):
            ok = np.asarray(self._health(ub, imgb, vals["yb"]))
        with span("serve.deliver"):
            out = []
            for i in range(width):
                if bool(ok[i]):
                    if i < B:
                        out.append((imgb[i], False))
                    continue
                # quarantine row i: re-initialize its carry slice in place
                # (rows are independent — every other client's result is
                # bitwise what it would have been without the poison).
                # Padded rows (i >= B) replicate the last session and must
                # be reset too, or the spill would hand it a poisoned carry.
                ub, xb = self._reset_row(ub, xb, i)
                if i < B:
                    self.quarantined += 1
                    out.append((Rejected("non-finite frame output; client "
                                         "quarantined, carry re-initialized"),
                                False))
            self._stack = (sids + (sids[-1],) * (width - B), ub, xb)
            self._by_sid = {s.sid: s for s in sessions}
        # NLINV streams are long-lived: never done from inside a tick
        return out

    def _health(self, ub, imgb, yb):
        """All-finite per batch row (carry, image, acquisition), fused
        into one jitted program."""
        if self._health_jit is None:
            @jax.named_scope("serve.health")
            def fn(u, img, y):
                ok = None
                for a in jax.tree.leaves(u) + [img, y]:
                    r = jnp.isfinite(a).all(
                        axis=tuple(range(1, a.ndim)))
                    ok = r if ok is None else ok & r
                return ok
            self._health_jit = jax.jit(fn)
        return self._health_jit(ub, imgb, yb)

    def _reset_row(self, ub, xb, i: int):
        """Fresh carry into batch row ``i`` of the stacked pytrees."""
        J, g = self._geom
        fresh = self.rec.init_carry(J, g)
        ub = jax.tree.map(lambda st, fr: st.at[i].set(fr), ub, fresh)
        xb = jax.tree.map(lambda st, fr: st.at[i].set(fr), xb, fresh)
        return ub, xb

    # -- elastic remesh ---------------------------------------------------
    def remesh(self, comm, sessions=()) -> None:
        """Continue every live stream on a survivor communicator (after
        ``Environment.survivor`` minted one for a device loss).

        The persistent stack is spilled, a new :class:`Reconstructor`
        is built on ``comm`` (plan keys carry the group token, so the
        survivor programs compile fresh), the pinned constants and every
        session carry in ``sessions`` migrate via
        ``repro.ft.migrate_carry`` — coil rows zero-padded to the new
        group size, which is exact for all NLINV sums — and subsequent
        ticks run at the survivor width.
        """
        self._spill()
        old = self.rec
        self.rec = Reconstructor(comm, newton=old.newton,
                                 cg_iters=old.cg_iters,
                                 channel_sum=old.channel_sum,
                                 hierarchical=old.hierarchical,
                                 fused=old.fused, overlap=old.overlap,
                                 slices=old.slices)
        self.remeshes += 1
        self._health_jit = None
        if self._geom is None:
            return
        J, g = self._geom
        size = self.rec.comm.size
        Jp = -(-J // size) * size
        self._geom = (Jp, g)
        self._fov_d = self.rec.put_const(np.asarray(self._fov_d))
        self._w_d = self.rec.put_const(np.asarray(self._w_d))
        for s in sessions:
            if s.done or not isinstance(s.state, dict):
                continue
            s.state["u"] = migrate_carry(self.rec, s.state["u"],
                                         pad_to=Jp)
            s.state["x_ref"] = migrate_carry(self.rec, s.state["x_ref"],
                                             pad_to=Jp)
            # staged uploads live on the LOST group: drop them (the
            # client resubmits; a dropped frame beats a dead stream)
            s.pending.clear()


class SlotPool:
    """Explicit KV-slot bookkeeping for continuous batching: ``assign``
    takes the lowest free slot, ``free`` returns it.  Every transition
    is checked — a double free or an over-assign is a bug in the caller,
    never silent state corruption."""

    def __init__(self, n: int):
        if n < 1:
            raise ValueError("SlotPool needs at least one slot")
        self.n = n
        self._free = list(range(n))
        self._used: set[int] = set()

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> tuple:
        return tuple(sorted(self._used))

    def assign(self) -> int:
        if not self._free:
            raise RuntimeError(f"SlotPool exhausted ({self.n} slots in use)")
        slot = self._free.pop(0)
        self._used.add(slot)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._used:
            raise RuntimeError(f"SlotPool.free({slot}): slot not assigned")
        self._used.remove(slot)
        self._free.append(slot)
        self._free.sort()


class LMDecodeWorkload(Workload):
    """Greedy LM decode as a Workload: one KV slot per admitted request,
    one decode step per work item.  Work items carry no payload (the
    token fed back is the previous output); results are token ids."""

    def __init__(self, cfg, params, *, batch: int = 4, max_len: int = 512):
        from ..models import transformer
        from .engine import make_serve_steps
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        pf, dec, _ = make_serve_steps(cfg, None, max_len=max_len, batch=1)
        self._prefill, self._decode = pf, dec
        self._mk_cache = lambda: transformer.init_cache(cfg, 1, max_len,
                                                        cfg.cdtype)
        self.slots = SlotPool(batch)

    def open_session(self, session: Session):
        from ..models import frontends
        prompt = list(session.meta["prompt"])
        slot = self.slots.assign()
        enc = frontends.synthetic_frontend(self.cfg, 1)
        cache = self._mk_cache()
        toks = jnp.asarray([prompt], jnp.int32)
        logits, cache = self._prefill(self.params, toks, cache, enc=enc)
        # the prefill emits the first output token at admission
        session.results.append(int(jnp.argmax(logits[0])))
        return {"slot": slot, "cache": cache, "pos": len(prompt)}

    def step(self, batch: list, width: int) -> list:
        out = []
        for session, _ in batch:
            st = session.state
            tok = jnp.asarray([[session.results[-1]]], jnp.int32)
            logits, st["cache"] = self._decode(self.params, tok,
                                               st["cache"], st["pos"])
            st["pos"] += 1
            nxt = int(jnp.argmax(logits[0]))
            produced = len(session.results) + 1   # incl. this token
            done = (produced >= int(session.meta["max_new"])
                    or st["pos"] >= self.max_len - 1)
            out.append((nxt, done))
        return out

    def close_session(self, session: Session) -> None:
        self.slots.free(session.state["slot"])

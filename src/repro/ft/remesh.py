"""Elastic remesh: continue live streams on a survivor submesh.

When a device is marked unhealthy (a :class:`~repro.ft.DeviceLossFault`
from the injector, or a real health signal), the recovery path is:

  1. ``Environment.survivor(comm, lost)`` mints a Communicator over the
     group's remaining devices;
  2. a new ``Reconstructor`` (or any group-bound program) is built on
     it — plan keys include the group token, so nothing stale is reused;
  3. every live Newton carry is re-placed onto the survivor group with
     :func:`migrate_carry` — the replicated ``rho`` re-broadcasts, the
     coil-segmented ``chat`` re-scatters through the same topology-aware
     upload routes ``put_frame`` always uses, zero-padding the coil dim
     to the survivor group size (zero channels are exact no-ops for all
     NLINV sums, so the continued stream matches the uninterrupted one).

``NlinvStreamWorkload.remesh`` drives steps 2–3 for a whole scheduler's
worth of sessions; this module holds the carry-level mechanics so the
checkpoint restore path (``repro.ckpt`` + ``resume_or_init``) can reuse
them: a carry restored from disk is migrated exactly like a live one.
"""

from __future__ import annotations

import numpy as np


def pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    """Zero-pad dim 0 of ``a`` up to ``rows`` (no-op when already
    there)."""
    if a.shape[0] >= rows:
        return a
    pad = np.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)
    return np.concatenate([a, pad], axis=0)


def migrate_carry(rec, u: dict, pad_to: int | None = None) -> dict:
    """Re-place one ``{rho, chat}`` Newton carry onto ``rec``'s group.

    ``rho`` is replicated (CLONE) — re-broadcast; ``chat`` is
    coil-segmented (NATURAL on ``rec.coil_dim``) — re-scattered, with its
    coil dim zero-padded to ``pad_to`` (default: the next multiple of the
    new group size).  Works on live carries and on host trees restored from
    a checkpoint alike (the leaves only need ``np.asarray``).
    """
    rho = np.asarray(u["rho"])
    chat = np.moveaxis(np.asarray(u["chat"]), rec.coil_dim, 0)
    size = rec.comm.size
    rows = pad_to if pad_to is not None else -(-chat.shape[0] // size) * size
    if rows % size:
        raise ValueError(
            f"carry migration needs the coil dim padded to a multiple of "
            f"the survivor group size {size}; got pad_to={pad_to}")
    chat = np.moveaxis(pad_rows(chat, rows), 0, rec.coil_dim)
    return {"rho": rec.put_const(rho), "chat": rec.put_frame(chat)}

"""Synchronization — the MGPU barrier/fence family (paper §2.5).

MGPU is asynchronous by default and offers ``barrier``/``fence``
functions built on condition variables + driver sync.  JAX is likewise
async by default (dispatch returns futures); the adaptation is:

  fence(x...)        host-blocks until the given arrays are computed
                     (driver-sync analogue, ``cudaStreamSynchronize``),
  barrier(group)     a collective no-op all devices must reach,
  barrier_fence()    both — the paper's strongest primitive,
  ordered(x, dep)    in-graph ordering: make ``x`` depend on ``dep``
                     without numerical effect (optimization_barrier), the
                     jit-compatible fence used to sequence collectives.

``group=`` accepts a ``DeviceGroup`` or an ``env.Communicator``; the
bound forms ``Communicator.barrier``/``fence``/``barrier_fence`` are the
stable surface (``barrier``/``barrier_fence`` here are their shims).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .runtime import DeviceGroup, current_group


def fence(*arrays):
    """Block the host until all pending ops producing ``arrays`` finish."""
    jax.block_until_ready(arrays)
    return arrays[0] if len(arrays) == 1 else arrays


def barrier(group: DeviceGroup | None = None) -> None:
    """All devices of the group reach this point (tiny psum round-trip)."""
    group = current_group(group)
    token = jnp.zeros((), jnp.int32)
    out = jax.shard_map(
        lambda t: lax.psum(t, group.axis_names if len(group.axis_names) > 1
                           else group.axis_names[0]),
        mesh=group.mesh, in_specs=P(), out_specs=P())(token)
    jax.block_until_ready(out)


def barrier_fence(*arrays, group: DeviceGroup | None = None):
    """MGPU ``barrier_fence()``: wait for pending ops, then barrier."""
    if arrays:
        fence(*arrays)
    barrier(group)
    return arrays[0] if len(arrays) == 1 else (arrays or None)


def ordered(x, dep):
    """Make ``x`` data-depend on ``dep`` inside jit (sequencing fence)."""
    x, _ = lax.optimization_barrier((x, dep))
    return x

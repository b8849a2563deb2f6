"""Segmented containers — the core MGPU abstraction, on JAX arrays.

An MGPU ``seg_dev_vector`` is one logical vector physically split across
device memories, carrying its own location metadata (a vector of
(pointer, size) tuples, Fig. 1 of the paper).  The JAX analogue keeps the
*global* ``jax.Array`` — whose shards already live on distinct devices —
and attaches the segmentation *policy* so that algorithms (comm verbs,
segmented FFT/BLAS, invoke_kernel) can reason about locality exactly the
way MGPU's hierarchical algorithms do.

Split policies (paper §2.2):
  NATURAL   contiguous even split along one dim,
  BLOCK     block-cyclic split (fixed block size, round-robin),
  CLONE     replicated on every device,
  OVERLAP2D contiguous row split with a halo of ``h`` rows exchanged
            with neighbours (for stencil-style kernels).
"""

from __future__ import annotations

import dataclasses
import enum
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from .runtime import DeviceGroup, current_group


class Policy(enum.Enum):
    NATURAL = "natural"
    BLOCK = "block"
    CLONE = "clone"
    OVERLAP2D = "overlap2d"


@dataclasses.dataclass(frozen=True)
class SegmentedArray:
    """A logically-global array with explicit segmentation metadata."""

    data: jax.Array
    group: DeviceGroup
    policy: Policy
    dim: int = 0                      # logical dim that is segmented
    mesh_axes: tuple[str, ...] = ("data",)
    orig_len: int | None = None       # pre-padding length along `dim`
    block: int | None = None          # BLOCK policy block size
    halo: int = 0                     # OVERLAP2D halo rows

    # -- basic queries ----------------------------------------------------
    @property
    def nseg(self) -> int:
        return self.group.axis_size(*self.mesh_axes)

    @property
    def global_shape(self) -> tuple[int, ...]:
        return tuple(self.data.shape)

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def pspec(self) -> P:
        if self.policy is Policy.CLONE:
            return P()
        spec: list[Any] = [None] * self.data.ndim
        spec[self.dim] = self.mesh_axes if len(self.mesh_axes) > 1 else self.mesh_axes[0]
        return P(*spec)

    @property
    def sharding(self) -> NamedSharding:
        return self.group.sharding(self.pspec)

    def seg_len(self, rank: int | None = None) -> int:
        """Per-segment length along the segmented dim.

        Without ``rank``: the uniform *physical* shard length (padding
        included).  With ``rank``: the *logical* length of that segment —
        block-cyclic remainders (BLOCK) and halo rows (OVERLAP2D)
        included, matching what MGPU's (pointer, size) metadata reports.
        """
        if rank is not None:
            return self._seg_sizes()[rank]
        if self.policy is Policy.CLONE:
            return self.data.shape[self.dim]
        return self.data.shape[self.dim] // self.nseg

    def _seg_sizes(self) -> list[int]:
        """Logical per-segment lengths along the segmented dim."""
        n = self.nseg
        total = self.data.shape[self.dim]
        orig = total if self.orig_len is None else self.orig_len
        if self.policy is Policy.CLONE:
            return [orig] * n
        if self.policy is Policy.BLOCK:
            # rank r owns blocks r, r+n, r+2n, ... of the padded sequence;
            # count only the elements below the pre-padding length.
            nblocks = total // self.block
            return [sum(max(0, min(orig - b * self.block, self.block))
                        for b in range(r, nblocks, n)) for r in range(n)]
        per = total // n                      # padded contiguous rows
        sizes = [max(0, min(orig - r * per, per)) for r in range(n)]
        if self.policy is Policy.OVERLAP2D and self.halo:
            # each segment additionally holds ``halo`` rows per existing
            # neighbour (edge segments have only one neighbour).
            h = self.halo
            sizes = [s + (h if r > 0 else 0) + (h if r < n - 1 else 0)
                     for r, s in enumerate(sizes)]
        return sizes

    def segments(self) -> list[tuple[int, ...]]:
        """MGPU's (pointer, size) tuple vector — here, per-segment shapes.

        Shapes are *logical*: BLOCK reports the block-cyclic remainder
        split and OVERLAP2D includes the halo rows exchanged with each
        existing neighbour.  One entry per segment (``nseg``) for every
        policy, CLONE included.
        """
        if self.policy is Policy.CLONE:
            return [self.global_shape] * self.nseg
        out = []
        for sz in self._seg_sizes():
            s = list(self.global_shape)
            s[self.dim] = sz
            out.append(tuple(s))
        return out

    # -- rewrap helpers ---------------------------------------------------
    def with_data(self, data: jax.Array) -> "SegmentedArray":
        return dataclasses.replace(self, data=data)

    # Elementwise arithmetic keeps segmentation (MGPU containers interoperate
    # with algorithms through iterators; here through jnp ops on .data).
    def _binop(self, other, op):
        o = other.data if isinstance(other, SegmentedArray) else other
        return self.with_data(op(self.data, o))

    def __add__(self, o): return self._binop(o, jnp.add)
    def __sub__(self, o): return self._binop(o, jnp.subtract)
    def __mul__(self, o): return self._binop(o, jnp.multiply)
    def __truediv__(self, o): return self._binop(o, jnp.divide)

    def astype(self, dt) -> "SegmentedArray":
        return self.with_data(self.data.astype(dt))

    # -- fluent verb surface (delegates to the owning communicator) -------
    # MGPU containers are arguments *to* communication methods bound to a
    # dev_group (paper Fig. 3); the fluent forms here resolve the owning
    # Communicator from the container's own group so algorithm code never
    # re-derives it.  Imports are deferred: comm/env import this module.
    @property
    def comm(self):
        """The owning :class:`repro.core.env.Communicator`."""
        from .env import Communicator
        return Communicator(self.group, self.mesh_axes)

    def to(self, policy: "Policy | None" = None, **kw) -> "SegmentedArray":
        """Re-segment under a new policy/dim (``comm.copy``).

        >>> from repro.core import Environment, Policy
        >>> seg = Environment().subgroup(1).container([1., 2.])
        >>> seg.to(Policy.CLONE).policy
        <Policy.CLONE: 'clone'>
        """
        from .comm import copy
        return copy(self, policy=policy, **kw)

    def gather(self) -> jax.Array:
        """Materialize the logical array (inverse of construction).

        >>> from repro.core import Environment
        >>> Environment().subgroup(1).container([1., 2.]).gather().tolist()
        [1.0, 2.0]
        """
        return gather(self)

    def reduce(self, op: str = "sum") -> jax.Array:
        """Merge the segments: the segmented dim is reduced away.

        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container([[1., 2.], [3., 4.]])
        >>> seg.reduce().tolist()
        [4.0, 6.0]
        """
        from .comm import reduce
        return reduce(self, op)

    def allreduce(self, op: str = "sum", *, hierarchical: bool = False,
                  p2p: bool = False) -> "SegmentedArray":
        """Reduce + replicate (-> CLONE container).

        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container([[1., 2.], [3., 4.]])
        >>> seg.allreduce().data.tolist()
        [4.0, 6.0]
        """
        from .comm import all_reduce
        return all_reduce(self, op, hierarchical=hierarchical, p2p=p2p)

    def allreduce_window(self, window=None, **kw) -> "SegmentedArray":
        """Windowed all-reduce: only ``window`` goes on the wire,
        scattered back into zeros (paper ``kern_all_red_p2p_2d``).

        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container([[1., 2., 3., 4.]])
        >>> seg.allreduce_window(((1, 3),)).data.tolist()
        [0.0, 2.0, 3.0, 0.0]
        """
        from .comm import all_reduce_window
        return all_reduce_window(self, window, **kw)

    def allgather(self) -> "SegmentedArray":
        """MPI_Allgather: the whole logical array, CLONEd.

        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container([1., 2., 3.])
        >>> seg.allgather().policy
        <Policy.CLONE: 'clone'>
        """
        from .comm import all_gather
        return all_gather(self)

    def reduce_scatter(self, op: str = "sum") -> "SegmentedArray":
        """Reduce the segments, leave the result segmented.

        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container([[1., 2.], [3., 4.]])
        >>> seg.reduce_scatter().gather().tolist()
        [4.0, 6.0]
        """
        from .comm import reduce_scatter
        return reduce_scatter(self, op)

    def alltoall(self, new_dim: int) -> "SegmentedArray":
        """Re-segment onto ``new_dim`` with an all-to-all.

        >>> import numpy as np
        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container(
        ...     np.zeros((2, 4), np.float32))
        >>> seg.alltoall(1).dim
        1
        """
        from .comm import all_to_all
        return all_to_all(self, new_dim)

    def vdot(self, other):
        """Inner product of the logical arrays (one reduction).

        >>> from repro.core import Environment
        >>> comm = Environment().subgroup(1)
        >>> float(comm.container([1., 2.]).vdot(comm.container([3., 4.])))
        11.0
        """
        from .comm import vdot
        return vdot(self, other)

    def shift(self, offset: int = 1, *, wrap: bool = True) -> "SegmentedArray":
        """Ring-shift segments by ``offset`` (p2p path); on a 1-segment
        ring the wrapped shift is the identity.

        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container([5., 6.])
        >>> seg.shift(1).gather().tolist()
        [5.0, 6.0]
        """
        from .comm import shift
        return shift(self, offset, wrap=wrap)

    def send_recv(self, perm) -> "SegmentedArray":
        """Pairwise segment exchange over ``(src, dst)`` pairs.

        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container([5., 6.])
        >>> seg.send_recv([(0, 0)]).gather().tolist()
        [5.0, 6.0]
        """
        from .comm import send_recv
        return send_recv(self, perm)

    def halo_exchange(self, fn: "Callable | None" = None) -> "SegmentedArray":
        """OVERLAP2D halo exchange over the p2p path.  With ``fn``: apply
        it to every halo-extended block (``(rows + 2h, ...) -> (rows,
        ...)``).  Without: return the halo-extended container itself
        (each segment physically carries its neighbours' rows, the
        paper's overlapped splitting of Fig. 1).

        A single segment has no neighbours, so its halo rows zero-fill:

        >>> from repro.core import Environment, Policy
        >>> seg = Environment().subgroup(1).container(
        ...     [[1., 1.], [2., 2.]], policy=Policy.OVERLAP2D, halo=1)
        >>> seg.halo_exchange().gather().tolist()
        [[0.0, 0.0], [1.0, 1.0], [2.0, 2.0], [0.0, 0.0]]
        """
        return overlap2d_map(self, fn)

    def invoke(self, fn: Callable, *args) -> "SegmentedArray":
        """Launch a shape-preserving kernel over this container's group
        with the local segment as first argument (``invoke_kernel_all``);
        the result inherits this container's segmentation.

        >>> from repro.core import Environment
        >>> seg = Environment().subgroup(1).container([1., 2.])
        >>> seg.invoke(lambda xl: xl * 10).gather().tolist()
        [10.0, 20.0]
        """
        from .invoke import invoke_kernel_all
        res = invoke_kernel_all(fn, self, *args, group=self.group,
                                out_specs=self.pspec,
                                mesh_axes=self.mesh_axes)
        return self.with_data(res.data if isinstance(res, SegmentedArray)
                              else res)


jax.tree_util.register_pytree_node(
    SegmentedArray,
    lambda s: ((s.data,), (s.group, s.policy, s.dim, s.mesh_axes,
                           s.orig_len, s.block, s.halo)),
    lambda aux, ch: SegmentedArray(ch[0], *aux))


# ---------------------------------------------------------------------------
# construction (MGPU: container ctor + implicit scatter)
# ---------------------------------------------------------------------------

def _pad_to(x: jax.Array, dim: int, mult: int) -> tuple[jax.Array, int]:
    n = x.shape[dim]
    target = math.ceil(n / mult) * mult
    if target == n:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[dim] = (0, target - n)
    return jnp.pad(x, pad), n


def _pad_to_np(x: np.ndarray, dim: int, mult: int) -> tuple[np.ndarray, int]:
    """numpy twin of ``_pad_to`` for the host-side segment() prologue."""
    n = x.shape[dim]
    target = math.ceil(n / mult) * mult
    if target == n:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[dim] = (0, target - n)
    return np.pad(x, pad), n


def _block_cyclic_perm(n: int, nseg: int, block: int) -> np.ndarray:
    """Permutation mapping logical index -> segment-major block-cyclic order."""
    nblocks = n // block
    ids = np.arange(n).reshape(nblocks, block)
    order = []
    for s in range(nseg):
        order.append(ids[s::nseg].reshape(-1))
    return np.concatenate(order)


def segment(x, group: DeviceGroup | None = None, *,
            policy: Policy = Policy.NATURAL, dim: int = 0,
            mesh_axes: tuple[str, ...] = ("data",), block: int | None = None,
            halo: int = 0) -> SegmentedArray:
    """Create a segmented container from a host/global array (MGPU ctor).

    The way data is split across devices is controlled here, exactly as in
    the paper's container constructor.
    """
    group = current_group(group)
    nseg = group.axis_size(*mesh_axes)
    # Host inputs (lists, numpy arrays) stay in numpy through the
    # pad/permute prologue so the single ``device_put`` at the end
    # uploads each shard straight to its owner — no staging hop through
    # device 0 of a committed full-array copy.  jax arrays and tracers
    # keep the jnp path (they may already live on-device or be abstract).
    on_host = not isinstance(x, (jax.Array, jax.core.Tracer))
    if on_host:
        x = np.asarray(x)
        x = x.astype(jax.dtypes.canonicalize_dtype(x.dtype), copy=False)
        xp, pad_to = np, _pad_to_np
    else:
        x = jnp.asarray(x)
        xp, pad_to = jnp, _pad_to

    if policy is Policy.CLONE:
        data = jax.device_put(x, group.sharding(P()))
        return SegmentedArray(data, group, policy, dim, mesh_axes,
                              orig_len=x.shape[dim] if x.ndim else None)

    if policy is Policy.BLOCK:
        if block is None:
            raise ValueError("BLOCK policy requires block=")
        x, orig = pad_to(x, dim, nseg * block)
        perm = _block_cyclic_perm(x.shape[dim], nseg, block)
        x = xp.take(x, perm if on_host else jnp.asarray(perm), axis=dim)
        seg = SegmentedArray(x, group, policy, dim, mesh_axes,
                             orig_len=orig, block=block)
    elif policy in (Policy.NATURAL, Policy.OVERLAP2D):
        x, orig = pad_to(x, dim, nseg)
        seg = SegmentedArray(x, group, policy, dim, mesh_axes,
                             orig_len=orig, halo=halo)
    else:
        raise ValueError(policy)

    data = jax.device_put(seg.data, seg.sharding)
    return seg.with_data(data)


def gather(seg: SegmentedArray) -> jax.Array:
    """Materialize the logical array (inverse of ``segment``)."""
    x = seg.data
    if seg.policy is Policy.BLOCK:
        perm = _block_cyclic_perm(x.shape[seg.dim], seg.nseg, seg.block)
        inv = np.argsort(perm)
        x = jnp.take(jax.device_put(x, seg.group.sharding(P())),
                     jnp.asarray(inv), axis=seg.dim)
    if seg.orig_len is not None and seg.orig_len != x.shape[seg.dim]:
        x = jax.lax.slice_in_dim(x, 0, seg.orig_len, axis=seg.dim)
    return jax.device_put(x, seg.group.sharding(P()))


# ---------------------------------------------------------------------------
# OVERLAP2D halo exchange (paper: "2D overlapped splitting")
# ---------------------------------------------------------------------------

def overlap2d_map(seg: SegmentedArray,
                  fn: Callable[[jax.Array], jax.Array] | None) -> SegmentedArray:
    """Halo exchange + map over an OVERLAP2D container.

    Each local row-block is extended by ``halo`` rows from its
    neighbours through the p2p path (two open-boundary ring ``shift``s —
    ``lax.ppermute``, the paper's P2P transfer; edge shards see zeros)
    and ``fn`` is applied to the extended block (``(rows + 2h, ...) ->
    (rows, ...)``).  ``fn=None`` returns the halo-extended container
    itself: a NATURAL container whose segments are the ``rows + 2h``
    blocks (MGPU's physically overlapped segments, Fig. 1).
    """
    if seg.policy is not Policy.OVERLAP2D:
        raise ValueError("overlap2d_map requires an OVERLAP2D container")
    from .comm import shift  # deferred: comm imports this module
    h = seg.halo
    axis = seg.mesh_axes[0]
    mesh = seg.group.mesh
    n = seg.nseg

    def body(x):
        # x: local block, segmented dim first for simplicity of slicing
        xm = jnp.moveaxis(x, seg.dim, 0)
        if h:
            # halo exchange == two open-boundary ring shifts: the top
            # rows travel up (+1), the bottom rows travel down (-1);
            # wrap=False zero-fills the edge shards.
            from_prev = shift(xm[-h:], +1, wrap=False, axis=axis, nseg=n)
            from_next = shift(xm[:h], -1, wrap=False, axis=axis, nseg=n)
            xm = jnp.concatenate([from_prev, xm, from_next], axis=0)
        ext = jnp.moveaxis(xm, 0, seg.dim)
        return ext if fn is None else fn(ext)

    spec = seg.pspec
    out = jax.shard_map(body, mesh=mesh, in_specs=spec,
                        out_specs=spec)(seg.data)
    if fn is None:
        return SegmentedArray(out, seg.group, Policy.NATURAL, seg.dim,
                              seg.mesh_axes, orig_len=out.shape[seg.dim])
    return seg.with_data(out)

"""Kernel invocation — the MGPU ``invoke_kernel`` family (paper §2.5).

MGPU forwards segmented containers to user kernels as *device ranges*
referencing only local memory, with a pass-through type when a kernel
needs the entire vector for peer-to-peer access.  The SPMD analogue:
``invoke_kernel_all`` shard_maps the user function so every argument
arrives as its local shard; ``PassThrough`` materializes the full array
(the TPU equivalent of P2P visibility is an all-gather); ``dev_rank``
is ``lax.axis_index``.

Every ``group=`` parameter accepts a ``DeviceGroup`` or an
``env.Communicator`` (whose group is unwrapped); the method forms
``Communicator.invoke``/``invoke_all``/``spmd`` are the stable surface.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .runtime import DeviceGroup, current_group
from .segmented import Policy, SegmentedArray


@dataclasses.dataclass(frozen=True)
class PassThrough:
    """Forward the *entire* segmented vector to the kernel (paper's
    pass-through type for peer-to-peer access)."""
    seg: SegmentedArray


def dev_rank(axis) -> jax.Array:
    """The calling shard's rank on ``axis`` (usable inside kernels)."""
    return lax.axis_index(axis)


def _unpack(args, group):
    in_specs, vals = [], []
    for a in args:
        if isinstance(a, SegmentedArray):
            in_specs.append(a.pspec)
            vals.append(a.data)
        elif isinstance(a, PassThrough):
            full = jax.device_put(a.seg.data, group.sharding(P()))
            in_specs.append(P())
            vals.append(full)
        else:
            in_specs.append(P())
            vals.append(jnp.asarray(a))
    return tuple(in_specs), tuple(vals)


def invoke_kernel_all(fn: Callable, *args,
                      group: DeviceGroup | None = None,
                      out_specs=None,
                      out_policy: Policy = Policy.NATURAL,
                      out_dim: int = 0,
                      mesh_axes: tuple[str, ...] | None = None,
                      probe_fn: Callable | None = None):
    """Launch ``fn`` on every device of the group (MGPU invoke_kernel_all).

    Segmented arguments are forwarded as local ranges; plain arrays and
    scalars are broadcast.  Returns a SegmentedArray when ``out_specs``
    segments the output, else the replicated array.
    """
    group = current_group(group)
    if mesh_axes is None:
        segs = [a for a in args if isinstance(a, SegmentedArray)]
        mesh_axes = segs[0].mesh_axes if segs else group.axis_names
    in_specs, vals = _unpack(args, group)
    if out_specs is None:
        out = [None] * _out_ndim_probe(probe_fn or fn, vals, in_specs, group)
        out[out_dim] = mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]
        out_specs = P(*out)
    res = jax.shard_map(fn, mesh=group.mesh, in_specs=in_specs,
                        out_specs=out_specs)(*vals)
    if out_specs == P() or all(s is None for s in out_specs):
        return res
    return SegmentedArray(res, group, out_policy, out_dim, tuple(mesh_axes))


def _out_ndim_probe(fn, vals, in_specs, group) -> int:
    """Infer output rank via abstract eval of the shard-local function."""
    local = []
    for v, s in zip(vals, in_specs):
        shape = list(v.shape)
        for d, ax in enumerate(s):
            if ax is not None:
                axes = ax if isinstance(ax, tuple) else (ax,)
                shape[d] //= group.axis_size(*axes)
        local.append(jax.ShapeDtypeStruct(tuple(shape), v.dtype))
    with group.mesh:
        out = jax.eval_shape(lambda *a: fn(*a), *local)
    return len(out.shape)


def _is_policy_leaf(p) -> bool:
    # (Policy, dim) pairs only — a tuple of bare Policy members is a
    # container (e.g. the out_policies of a two-output kernel).
    return isinstance(p, Policy) or (
        isinstance(p, tuple) and len(p) == 2
        and isinstance(p[0], Policy) and isinstance(p[1], int))


def policy_pspec(p, axis) -> P:
    """Map a segmentation policy leaf — ``Policy`` or ``(Policy, dim)`` —
    to its PartitionSpec."""
    dim = 0
    if isinstance(p, tuple):
        p, dim = p
    if p is Policy.CLONE:
        return P()
    return P(*([None] * dim + [axis]))


def make_spmd(fn: Callable, group: DeviceGroup | None = None, *,
              in_policies, out_policies,
              mesh_axes: tuple[str, ...] = ("data",),
              check_vma: bool = True, donate_argnums=(), jit: bool = True):
    """Compile an SPMD kernel from segmentation *policies* (paper §2.5's
    ``invoke_kernel_all`` for algorithms, not arrays).

    ``in_policies`` is one pytree per positional argument and
    ``out_policies`` one for the result; leaves are ``Policy`` members or
    ``(Policy, dim)`` pairs (``Policy`` alone segments dim 0).  The body
    sees local shards and may call the verbs' in-shard_map forms
    (``Communicator.allreduce_window`` etc.).  Downstream layers never
    construct a PartitionSpec or touch shard_map: ``Communicator.spmd``
    is the single launch point the container layer exposes (this free
    function is its deprecated-shim engine).

    A 1-device group is the degenerate case — same program, the
    collectives are no-ops — which is how single- and multi-device
    callers share one code path.
    """
    group = current_group(group)
    axis = mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]
    to_specs = lambda pol: jax.tree.map(lambda p: policy_pspec(p, axis),
                                        pol, is_leaf=_is_policy_leaf)
    sm = jax.shard_map(fn, mesh=group.mesh,
                       in_specs=tuple(to_specs(p) for p in in_policies),
                       out_specs=to_specs(out_policies),
                       check_vma=check_vma)
    if not jit:
        if donate_argnums:
            raise ValueError("donate_argnums requires jit=True")
        return sm
    return jax.jit(sm, donate_argnums=donate_argnums)


def invoke_kernel(fn: Callable, *args, rank: int,
                  group: DeviceGroup | None = None, **kw):
    """Launch ``fn`` only in the context of device ``rank`` (flat index).

    SPMD adaptation: the kernel body executes on every shard (lockstep
    programs cannot diverge) but its effect is masked to ``rank``; other
    shards contribute zeros.  Matches MGPU semantics where only the
    target device's segment is written.
    """
    group = current_group(group)
    sizes = [group.mesh.shape[a] for a in group.axis_names]

    def masked(*local_args):
        idx = 0
        for a in group.axis_names:
            idx = idx * group.mesh.shape[a] + lax.axis_index(a)
        out = fn(*local_args)
        return jnp.where(idx == rank, out, jnp.zeros_like(out))

    return invoke_kernel_all(masked, *args, group=group, probe_fn=fn, **kw)

"""Runtime environment — the MGPU ``environment`` / ``dev_group`` analogue.

MGPU instantiates an ``environment`` that detects the devices in the node
and lets the user restrict computation to a ``dev_group``.  On TPU the
equivalent object is a named-axis mesh: the environment builds a
``jax.Mesh`` from the available devices, classifies each axis as ICI
(intra-pod, fast) or DCN (inter-pod, slow) — the direct analogue of the
paper's PCIe-domain / IOH-boundary distinction — and supports submesh
selection (the ``dev_group`` constructor argument).
"""

from __future__ import annotations

import dataclasses
import math
import os
import pathlib
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from . import compat

# Axis names that cross the data-center network rather than ICI.  The
# paper's topology split (P2P inside an IOH vs. host-staged across IOHs)
# maps onto this boundary.
DCN_AXES = ("pod",)

# TPU v5e hardware model used for all analytic/roofline derivations.
HW = dict(
    peak_flops_bf16=197e12,  # FLOP/s per chip
    hbm_bw=819e9,            # bytes/s per chip
    ici_bw=50e9,             # bytes/s per link (intra-pod)
    dcn_bw=25e9,             # bytes/s per chip (inter-pod, conservative)
    vmem_bytes=128 * 2**20,  # VMEM per chip
    hbm_bytes=16 * 2**30,    # HBM per chip
)


@dataclasses.dataclass(frozen=True)
class DeviceGroup:
    """A named-axis device group (MGPU ``dev_group``)."""

    mesh: Mesh

    # -- constructors -----------------------------------------------------
    @classmethod
    def all_devices(cls, shape: Sequence[int] | None = None,
                    axes: Sequence[str] = ("data",)) -> "DeviceGroup":
        """Build a group over every addressable device (MGPU default ctor)."""
        ndev = len(jax.devices())
        if shape is None:
            shape = (ndev,)
        if math.prod(shape) != ndev:
            raise ValueError(f"mesh shape {shape} != device count {ndev}")
        return cls(compat.make_mesh(tuple(shape), tuple(axes)))

    @classmethod
    def subset(cls, n: int, axes: Sequence[str] = ("data",)) -> "DeviceGroup":
        """Restrict to the first ``n`` devices (MGPU ``dev_group`` ctor)."""
        avail = jax.devices()
        if n > len(avail):
            raise ValueError(
                f"requested {n} devices, host has {len(avail)} (simulate "
                f"more with XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        devs = np.asarray(avail[:n]).reshape((n,))
        return cls(Mesh(devs, tuple(axes)))

    @classmethod
    def from_mesh(cls, mesh: Mesh) -> "DeviceGroup":
        return cls(mesh)

    # -- queries ----------------------------------------------------------
    @property
    def ndev(self) -> int:
        return self.mesh.size

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def shape(self) -> Mapping[str, int]:
        return dict(self.mesh.shape)

    @property
    def ici_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.axis_names if a not in DCN_AXES)

    @property
    def dcn_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.axis_names if a in DCN_AXES)

    @property
    def platform(self) -> str:
        return self.mesh.devices.flat[0].platform

    @property
    def unified_memory(self) -> bool:
        """True when the group's devices share one memory domain (the
        host-simulated CPU mesh): a host->device upload or replicated
        ``device_put`` is then a local copy, so bandwidth-splitting
        schedules (scatter+allgather broadcast, psum_scatter+all_gather
        reduce) only add collective rounds.  The transfer layer picks
        direct schedules here and the decomposed ones on discrete-memory
        accelerator platforms."""
        return self.platform == "cpu"

    def axis_size(self, *axes: str) -> int:
        return math.prod(self.mesh.shape[a] for a in axes)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def __enter__(self):
        self._ctx = self.mesh
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)


REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (scripts and examples call this first; library imports never do, so
    tests stay cache-free).  ``JAX_COMPILATION_CACHE_DIR``, when set,
    already places the cache and is left alone; otherwise the cache is
    the fixed ``.jax_cache/`` at the repository root — a fixed path,
    because the directory is part of what a later run must find again.
    Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def span(name: str, **ids):
    """A host span ``repro.<name>`` on the profiler's clock: a
    ``jax.profiler.TraceAnnotation`` (about a microsecond when no
    profiler runs).  Spans nest on the calling thread; ``ids`` (``sid``,
    ``tick``, ``width``, ``frame``) are what the spans of one frame
    share."""
    return jax.profiler.TraceAnnotation(f"repro.{name}", **ids)


def current_group(group=None) -> DeviceGroup:
    """Default-group resolution: explicit arg > ambient mesh > all devices.

    .. deprecated:: PR 2
        The implicit-global-group idiom is deprecated.  Hold an
        ``env.Communicator`` (whose group is always explicit) instead.
        This resolver remains as the engine of the free-function shims.

    ``group`` may be a ``DeviceGroup`` or anything carrying one under a
    ``.group`` attribute (an ``env.Communicator``).
    """
    if group is not None:
        return getattr(group, "group", group)
    mesh = compat.ambient_mesh()  # inside a `with mesh:` scope
    if mesh is not None:
        return DeviceGroup(mesh)
    return DeviceGroup.all_devices()

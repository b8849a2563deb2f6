"""Quickstart: the MGPU-style core API in 60 lines.

    PYTHONPATH=src python examples/quickstart.py

Mirrors the paper's §2 walk-through: create an environment, bind a
communicator to a device group, build segmented containers, move data
with the MPI-like verb *methods* (collectives + point-to-point), call
segmented FFT/BLAS, and launch a custom kernel on every device.  Run
with XLA_FLAGS=--xla_force_host_platform_device_count=8 to see real
multi-device segmentation on CPU.
"""

import numpy as np

import jax.numpy as jnp
from repro.core import Environment, Policy
from repro.core.runtime import use_compile_cache
from repro.lib import blas, fft, plan_stats

use_compile_cache()

# -- environment / dev_group (paper §2.1) ----------------------------------
env = Environment()
comm = env.world                       # all devices, one "data" axis
print(f"environment: {env}; communicator: {comm}")

# -- segmented containers (paper §2.2) --------------------------------------
x = np.random.randn(8, 64, 64).astype(np.complex64)   # 8 matrices
seg = comm.container(x)                                # natural split
print("segments:", seg.segments()[0], "x", seg.nseg)

clone = comm.bcast(x[0])                               # CLONE policy
blocks = comm.container(x, policy=Policy.BLOCK, block=2)
assert np.allclose(comm.gather(blocks), x)

# -- MPI-like communication (paper §2.3, Fig. 3) ----------------------------
summed = comm.reduce(seg)               # one matrix: sum over segments
summed_everywhere = seg.allreduce()     # ... CLONEd on every device
print("reduce == sum:", np.allclose(summed, x.sum(0), atol=1e-4))
full = seg.allgather()                  # MPI_Allgather -> CLONE container
print("allgather:", np.allclose(np.asarray(full.data), x, atol=0))

# -- point-to-point (paper's P2P path; lax.ppermute) ------------------------
ring = seg.shift(1)                     # each segment to the next device
print("shift ring:", comm.gather(ring).shape, "(segments rotated by 1)")
pairs = [(0, 1), (1, 0)] if comm.size > 1 else [(0, 0)]
swapped = comm.send_recv(seg, pairs)    # pairwise exchange
print("send_recv:", swapped.global_shape)

# -- ported libraries (paper §2.4/§4: plan once, call many) ------------------
k = fft.fft2_batched(seg, centered=True)               # builds the FFT plan
img = fft.fft2_batched(k, inverse=True, centered=True)
print("fft roundtrip:", np.allclose(comm.gather(img), x, atol=1e-4))

y = comm.container(np.random.randn(8, 64, 64).astype(np.complex64))
z = blas.axpy(2.0 + 1j, seg, y)                        # a*X + Y
print("dot <x,y> =", complex(blas.dot(seg, y)))
w, d = blas.axpy_dot(0.5, seg, y, y)                   # fused epilogue
print("plan cache:", plan_stats())                     # hits/builds/hit_rate

# -- invoke_kernel (paper §2.5) ----------------------------------------------
def my_kernel(xl, yl):                  # receives local ranges
    return jnp.abs(xl) ** 2 + jnp.abs(yl) ** 2

power = comm.invoke_all(my_kernel, seg, y)
print("invoke_all ->", power.global_shape, power.data.dtype)
print("quickstart OK")

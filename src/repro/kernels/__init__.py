"""Pallas TPU kernels for the framework's compute hot-spots.

Each subpackage is a kernel triplet: kernel.py (pl.pallas_call +
BlockSpec VMEM tiling), ops.py (jit'd wrapper registering one or more
:class:`~repro.kernels.registry.KernelSpec` entries), ref.py (pure-jnp
oracle).  ``registry.py`` is the shared surface: spec-driven dispatch
(backend routing + block eligibility + block-size choice), a
PlanCache-backed block autotuner, and auto-discovery that the shared
parity/property harness in ``tests/test_kernel_registry.py`` runs on.

  flash_attention   tiled online-softmax attention (causal/window/softcap/GQA)
  mlstm             chunkwise matrix-memory mLSTM (xLSTM)
  rg_lru            blocked linear recurrence (RecurrentGemma)
  coil_mult         NLINV coil pointwise C / fused channel-summed C^H
  gridding          separable-matrix (de)gridding as MXU matmuls
  cg_fused          single-pass CG updates with dot epilogues
  dft               centred 2-D DFT as MXU matmuls (r x 128 factorisation)
  masked_allreduce  fused masked partial-image sum (kern_all_red_p2p_2d)
"""

from . import registry

__all__ = ["registry"]

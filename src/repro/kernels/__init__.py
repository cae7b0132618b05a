"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel ships three pieces (assignment contract):
  <name>.py   pl.pallas_call + explicit BlockSpec VMEM tiling
  ops.py      jit'd public wrappers (backend dispatch; library code
              calls these, never kernels directly)
  ref.py      pure-jnp oracles (the allclose ground truth)

Kernels:
  sfc_keys         Morton/Hilbert key generation -- the paper's
                   partitioning hot spot (bit ops over VMEM tiles)
  prefix_scan      blocked exclusive prefix sum -- Algorithm 1's S_i /
                   MoE capacity offsets (VMEM carry across grid steps)
  ksection_hist    fused k-section candidate-cut weight histogram --
                   the distributed partitioner's per-round reduction
                   (streaming compare-accumulate, no sort/scatter)
  flash_attention  blocked online-softmax attention (causal/SWA/GQA) --
                   the LM substrate's dominant compute at 32k prefill
  fem_matvec       fused P1 element matvec (gather -> precomputed-4x4
                   apply -> scatter-accumulate as one-hot matmuls over
                   a per-tile window of a locality-ordered part) --
                   the owned-layout FEM hot path's per-call element work
  serve_prefill    segment-masked packed-prefill attention -- the serving
                   engine's batched-admission hot loop (one launch over
                   the fixed-capacity packed buffer, per-request causal
                   bands via segment-range tile early-out)

All validated in interpret mode on CPU (tests/test_kernels.py) over
shape/dtype sweeps; tests/test_tpu_compile.py compiles the main-path
kernels for a described TPU v5e at real sizes, and chip_smoke.py runs
sfc_keys, ksection_hist and fem_matvec compiled on the chip.
"""
from .ops import (exclusive_scan_op, fem_matvec_op, flash_attention_op,
                  ksection_histogram_op, packed_attention_op, sfc_keys_op)

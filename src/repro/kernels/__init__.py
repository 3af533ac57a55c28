"""Pallas TPU kernels for the perf-critical compute of ultra-low-bit serving:

- ``quant_matmul``: fused dequant (packed 1-8 bit) + MXU matmul — the serving
  hot loop; cuts weight HBM traffic by the packing factor.
- ``group_quant``: fused group quant->dequant roundtrip — the discrete
  search's inner primitive (one VMEM pass instead of four HBM passes).
- ``transform_quant``: fused (π, s, φ) invariant transform + group
  fake-quant — the population search's per-proposal hot path; one VMEM pass
  instead of materialize-transformed-weights-then-quantize (two full HBM
  round trips).
- ``flash_attention``: full-sequence causal or plain self-attention with
  the score tiles kept in VMEM and the tiles above the causal diagonal never
  computed — the no-cache forward's attention on a TPU (training, the
  search's calibration forwards).
- ``flash_decode`` / ``paged_decode``: fused one-token decode attention over
  a contiguous (flash) or block-table-paged (paged) KV cache; the paged
  variant scalar-prefetches the block table so continuous batching reads
  only live pages.

Each kernel has a pure-jnp oracle in ``ref.py``; ``ops.py`` wraps them with
jit + CPU interpret-mode fallback; tests sweep shapes/dtypes against the
oracles.
"""
from repro.kernels.ops import (quant_matmul, group_quant, flash_attention,
                               flash_decode, paged_decode, transform_quant,
                               on_tpu)

__all__ = ["quant_matmul", "group_quant", "flash_attention", "flash_decode",
           "paged_decode", "transform_quant", "on_tpu"]

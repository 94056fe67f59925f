"""Pallas TPU kernels for the perf-critical hot spots, with jnp oracles.

  steepest_neighbor  — DPC init stencil (Alg. 1 l. 3-5), VMEM-tiled argmax
  fused_local_phase  — pointer init + ghost override in ONE kernel (the
                       block-local phase of Alg. 1/3; the one kernel on a
                       topology path, dispatched by ops.py)
  block_pathcompress — K in-VMEM doubling rounds (thread-local compression)
  flash_attention    — fused online-softmax attention for the LM substrate
  segment_bag        — fused EmbeddingBag (vocab-tiled gather+reduce),
                       the recsys lookup hot path
"""
from . import ops, ref
from .steepest_neighbor import steepest_neighbor
from .fused_local_phase import fused_local_phase
from .block_pathcompress import block_pathcompress
from .flash_attention import flash_attention
from .segment_bag import segment_bag

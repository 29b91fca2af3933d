"""Pallas TPU kernels for ops XLA does not fuse well enough on its own.

The reference's equivalent layer is the hand-written CUDA kernel zoo in
src/tensors/gpu/ (element.cu, tensor_operators.cu, prod.cpp). Here almost
all of that collapses into XLA fusion; the kernels that remain are the ones
where *blockwise scheduling across the memory hierarchy* (HBM->VMEM) is the
win: flash attention for long sequences and the fused beam-gather +
cache-read decode step. Head-packed attention for short sequences is
kept behind `--transformer-packed-attention on`: it lost to XLA (PR 52).
"""

from .decode_attention import decode_attention  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .packed_attention import packed_attention  # noqa: F401

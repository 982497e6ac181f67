"""Pallas TPU kernels for hot ops (the libnd4j/cuDNN-custom-kernel seam,
TPU-native: hand-written Mosaic kernels where XLA's automatic lowering
leaves throughput on the table)."""
from .sparse_attention import flash_attention

__all__ = ["flash_attention"]

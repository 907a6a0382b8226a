"""tpu_dist.ops — custom Pallas TPU kernels (the cuDNN-extension analogue).

The reference's hot ops live in cuDNN/ATen (SURVEY.md §2b #15); tpu_dist gets
them from XLA, and this package holds the hand-written Pallas kernels for the
cases worth owning: ops where fusion XLA can't see saves HBM traffic."""

from .cross_entropy import fused_cross_entropy
from .flash_attention import (flash_attention, flash_attention_heads_first,
                              flash_attention_with_lse)
from .gmm import gmm, grouped_linear, tgmm

__all__ = ["fused_cross_entropy", "flash_attention",
           "flash_attention_heads_first", "flash_attention_with_lse", "gmm",
           "grouped_linear", "tgmm"]

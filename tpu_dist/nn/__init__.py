"""tpu_dist.nn — functional module system + layers (L2 of the layer map,
SURVEY.md §1)."""

from . import cache, functional, init
from .attention import (MultiheadSelfAttention, attention_impl, rotary_embed,
                        scaled_dot_product_attention, yarn_inv_freq,
                        yarn_mscale)
from .deltanet import GatedDeltaNet, KimiDeltaAttention
from .hybrid import ParallelMixer
from .hyper import HyperConnection, close_streams, open_streams
from .layers import (AdaptiveAvgPool2d, AvgPool2d, BatchNorm2d, Conv2d,
                     Dropout, Embedding, Flatten, GELU, GatedMLP, Identity,
                     LayerNorm, Linear, MaxPool2d, ReLU, RMSNorm)
from .loss import CrossEntropyLoss
from .mamba import Mamba2
from .mla import MultiheadLatentAttention
from .moe import MoELayer
from .module import Module, Remat, Sequential, run_capturing_state
from .quant import (QuantEmbedding, QuantLinear,
                    QuantMultiheadSelfAttention, quantize_linear_weights)
from .shortconv import GatedShortConv

__all__ = [
    "Module", "Remat", "Sequential", "run_capturing_state",
    "cache", "functional", "init",
    "Linear", "Conv2d", "MaxPool2d", "AvgPool2d", "AdaptiveAvgPool2d",
    "ReLU", "Flatten", "Dropout", "BatchNorm2d", "Identity",
    "Embedding", "LayerNorm", "RMSNorm", "GELU", "GatedMLP",
    "MultiheadSelfAttention", "MultiheadLatentAttention",
    "scaled_dot_product_attention", "attention_impl", "GatedDeltaNet",
    "KimiDeltaAttention", "Mamba2", "GatedShortConv", "ParallelMixer",
    "HyperConnection", "open_streams", "close_streams",
    "MoELayer", "rotary_embed", "yarn_inv_freq", "yarn_mscale",
    "CrossEntropyLoss",
    "QuantEmbedding", "QuantLinear", "QuantMultiheadSelfAttention",
    "quantize_linear_weights",
]

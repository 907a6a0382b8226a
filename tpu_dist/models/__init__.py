"""tpu_dist.models — reference workload architectures."""

from .convnet import ConvNet
from .falcon_h1 import FalconH1LM
from .kimi_k2 import KimiK2LM
from .kimi_linear import KimiLinearLM
from .lfm2_moe import Lfm2MoeLM
from .resnet import ResNet, resnet18, resnet34, resnet50
from .qwen3_next import Qwen3NextLM
from .transformer import TransformerBlock, TransformerLM
from .xing4 import Xing4LM
from .vgg import (VGG, vgg11, vgg11_bn, vgg13, vgg13_bn, vgg16, vgg16_bn,
                  vgg19, vgg19_bn)
from .vit import VisionTransformer, vit_b_16, vit_b_32, vit_l_16, vit_l_32

__all__ = ["ConvNet", "ResNet", "resnet18", "resnet34", "resnet50",
           "TransformerLM", "TransformerBlock", "Qwen3NextLM", "KimiK2LM",
           "Xing4LM", "KimiLinearLM", "FalconH1LM", "Lfm2MoeLM",
           "VGG", "vgg11", "vgg13", "vgg16", "vgg19",
           "vgg11_bn", "vgg13_bn", "vgg16_bn", "vgg19_bn",
           "VisionTransformer", "vit_b_16", "vit_b_32", "vit_l_16",
           "vit_l_32"]

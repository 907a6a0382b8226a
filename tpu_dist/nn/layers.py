"""Parameterized layers (TPU-native equivalents of the torch layers the
reference uses: Conv2d/MaxPool2d/ReLU/Linear/Dropout at
/root/reference/mpspawn_dist.py:11-43, BatchNorm inside torchvision ResNet-18
at /root/reference/example_mp.py:50).

Layouts are TPU-first: activations NHWC, conv kernels HWIO, linear weights
(in, out).  Default initialization matches torch's defaults in distribution.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from . import functional as F
from . import init as init_lib
from .module import Module, _ctx

__all__ = [
    "Linear", "Conv2d", "MaxPool2d", "AvgPool2d", "AdaptiveAvgPool2d",
    "ReLU", "Flatten", "Dropout", "BatchNorm2d", "Identity",
    "Embedding", "LayerNorm", "GELU", "GatedMLP",
]

_IntOr2 = Union[int, Tuple[int, int]]


class Linear(Module):
    def __init__(self, in_features: int, out_features: int, bias: bool = True):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = bias

    def create_params(self, key):
        kw, kb = jax.random.split(key)
        p = {"weight": init_lib.torch_default_uniform(
            kw, (self.in_features, self.out_features), self.in_features)}
        if self.use_bias:
            p["bias"] = init_lib.torch_default_uniform(
                kb, (self.out_features,), self.in_features)
        return p

    def forward(self, x):
        p = _ctx().get_params(self._path)
        return F.linear(x, p["weight"], p.get("bias"))

    def __repr__(self):
        return f"Linear(in={self.in_features}, out={self.out_features})"


class Conv2d(Module):
    def __init__(self, in_channels: int, out_channels: int,
                 kernel_size: _IntOr2, stride: _IntOr2 = 1,
                 padding: _IntOr2 = 0, dilation: _IntOr2 = 1,
                 groups: int = 1, bias: bool = True):
        super().__init__()
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.groups = groups
        self.use_bias = bias

    def create_params(self, key):
        kh, kw_ = self.kernel_size
        shape = (kh, kw_, self.in_channels // self.groups, self.out_channels)
        fan_in = kh * kw_ * (self.in_channels // self.groups)
        k1, k2 = jax.random.split(key)
        p = {"weight": init_lib.torch_default_uniform(k1, shape, fan_in)}
        if self.use_bias:
            p["bias"] = init_lib.torch_default_uniform(k2, (self.out_channels,), fan_in)
        return p

    def forward(self, x):
        p = _ctx().get_params(self._path)
        return F.conv2d(x, p["weight"], p.get("bias"), stride=self.stride,
                        padding=self.padding, dilation=self.dilation,
                        groups=self.groups)

    def __repr__(self):
        return (f"Conv2d({self.in_channels}, {self.out_channels}, "
                f"kernel={self.kernel_size}, stride={self.stride}, "
                f"padding={self.padding})")


class MaxPool2d(Module):
    def __init__(self, kernel_size: _IntOr2, stride: Optional[_IntOr2] = None,
                 padding: _IntOr2 = 0):
        super().__init__()
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding

    def forward(self, x):
        return F.max_pool2d(x, self.kernel_size, self.stride, self.padding)

    def __repr__(self):
        return f"MaxPool2d(kernel={self.kernel_size}, stride={self.stride})"


class AvgPool2d(Module):
    def __init__(self, kernel_size: _IntOr2, stride: Optional[_IntOr2] = None,
                 padding: _IntOr2 = 0):
        super().__init__()
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding

    def forward(self, x):
        return F.avg_pool2d(x, self.kernel_size, self.stride, self.padding)


class AdaptiveAvgPool2d(Module):
    """Average-pool NHWC to a fixed (h, w) output (torchvision ResNet head)."""

    def __init__(self, output_size: _IntOr2 = 1):
        super().__init__()
        self.output_size = (output_size, output_size) if isinstance(output_size, int) else tuple(output_size)

    def forward(self, x):
        oh, ow = self.output_size
        n, h, w, c = x.shape
        if h % oh == 0 and w % ow == 0:
            return F.avg_pool2d(x, (h // oh, w // ow))
        # general torch bin rule — output cell i averages input rows
        # [floor(i*H/out), ceil((i+1)*H/out)); covers non-divisible shapes
        # AND output > input (e.g. torchvision VGG pooling 1x1 -> 7x7 on
        # CIFAR inputs).  Static Python loop: oh + ow row/col reductions,
        # fixed at trace time, fused by XLA.
        rows = jnp.stack([
            x[:, (i * h) // oh: -((-(i + 1) * h) // oh)].mean(axis=1)
            for i in range(oh)], axis=1)                     # (n, oh, w, c)
        return jnp.stack([
            rows[:, :, (j * w) // ow: -((-(j + 1) * w) // ow)].mean(axis=2)
            for j in range(ow)], axis=2)                     # (n, oh, ow, c)


class ReLU(Module):
    def forward(self, x):
        return F.relu(x)

    def __repr__(self):
        return "ReLU()"


class Identity(Module):
    def forward(self, x):
        return x


class Flatten(Module):
    def __init__(self, start_dim: int = 1):
        super().__init__()
        self.start_dim = start_dim

    def forward(self, x):
        return F.flatten(x, self.start_dim)


class Dropout(Module):
    """Inverted dropout; active only in training mode (requires apply rng=).

    Note the reference ConvNet *defines* ``nn.Dropout(p=0.5)`` but never calls
    it in forward (/root/reference/mpspawn_dist.py:31 — dead layer); the ported
    ConvNet reproduces that faithfully.
    """

    def __init__(self, p: float = 0.5):
        super().__init__()
        self.p = p

    def forward(self, x):
        ctx = _ctx()
        if not ctx.training or self.p == 0.0:
            return x
        return F.dropout(x, self.p, ctx.next_rng(), training=True)

    def __repr__(self):
        return f"Dropout(p={self.p})"


class BatchNorm2d(Module):
    """Batch normalization over NHWC with torch semantics.

    - training: normalize with biased batch stats; update running stats with
      *unbiased* variance, ``running = (1-momentum)*running + momentum*batch``.
    - eval: normalize with running stats.
    - ``axis_name``: if set and traced inside ``shard_map``/``pmap`` with that
      mesh axis, batch statistics are ``pmean``-ed across replicas (SyncBN).
      Default ``None`` matches DDP's per-replica (non-synced) BatchNorm — the
      reference's ResNet-18 behavior under DDP (/root/reference/example_mp.py:53
      wraps without SyncBatchNorm conversion).
    """

    def __init__(self, num_features: int, eps: float = 1e-5,
                 momentum: float = 0.1, affine: bool = True,
                 track_running_stats: bool = True,
                 axis_name: Optional[str] = None):
        super().__init__()
        self.num_features = num_features
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        self.track_running_stats = track_running_stats
        self.axis_name = axis_name

    def create_params(self, key):
        if not self.affine:
            return None
        return {"weight": jnp.ones((self.num_features,)),
                "bias": jnp.zeros((self.num_features,))}

    def create_state(self):
        if not self.track_running_stats:
            return None
        return {"mean": jnp.zeros((self.num_features,)),
                "var": jnp.ones((self.num_features,))}

    def forward(self, x):
        ctx = _ctx()
        p = ctx.get_params(self._path) if self.affine else {}
        reduce_axes = tuple(range(x.ndim - 1))  # all but channel
        if ctx.training or not self.track_running_stats:
            mean = x.mean(reduce_axes)
            mean2 = (x * x).mean(reduce_axes)
            if self.axis_name is not None:
                mean = lax.pmean(mean, self.axis_name)
                mean2 = lax.pmean(mean2, self.axis_name)
            var = mean2 - mean * mean
            if self.track_running_stats:
                st = ctx.get_state(self._path)
                n = x.size // x.shape[-1]
                if self.axis_name is not None:
                    n = n * lax.psum(1, self.axis_name)
                unbiased = var * (n / max(n - 1, 1))
                m = self.momentum
                ctx.put_state(self._path, {
                    "mean": (1 - m) * st["mean"] + m * mean,
                    "var": (1 - m) * st["var"] + m * unbiased,
                })
        else:
            st = ctx.get_state(self._path)
            mean, var = st["mean"], st["var"]
        return F.batch_norm(x, mean, var, p.get("weight"), p.get("bias"),
                            self.eps)

    def __repr__(self):
        return f"BatchNorm2d({self.num_features})"


class Embedding(Module):
    """Token embedding lookup (torch ``nn.Embedding`` parity; N(0,1) init).

    Divergence from torch: out-of-range indices are CLAMPED to the last row
    (XLA gather semantics under jit — no device-side bounds trap exists on
    TPU), where torch raises IndexError.  Validate token ids host-side when
    the vocabulary mapping is untrusted.
    """

    def __init__(self, num_embeddings: int, embedding_dim: int):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim

    def create_params(self, key):
        return {"weight": init_lib.normal(
            key, (self.num_embeddings, self.embedding_dim), std=1.0)}

    def forward(self, idx):
        w = _ctx().get_params(self._path)["weight"]
        return jnp.take(w, idx, axis=0)

    def __repr__(self):
        return f"Embedding({self.num_embeddings}, {self.embedding_dim})"


class LayerNorm(Module):
    """Layer normalization over the trailing dimension(s)
    (torch ``nn.LayerNorm`` parity: biased variance, affine by default)."""

    def __init__(self, normalized_shape, eps: float = 1e-5,
                 elementwise_affine: bool = True):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.eps = eps
        self.elementwise_affine = elementwise_affine

    def create_params(self, key):
        if not self.elementwise_affine:
            return None
        return {"weight": jnp.ones(self.normalized_shape),
                "bias": jnp.zeros(self.normalized_shape)}

    def forward(self, x):
        axes = tuple(range(x.ndim - len(self.normalized_shape), x.ndim))
        mean = x.mean(axes, keepdims=True)
        var = ((x - mean) ** 2).mean(axes, keepdims=True)
        y = (x - mean) * lax.rsqrt(var + self.eps)
        if self.elementwise_affine:
            p = _ctx().get_params(self._path)
            y = y * p["weight"] + p["bias"]
        return y

    def __repr__(self):
        return f"LayerNorm({self.normalized_shape})"


class RMSNorm(Module):
    """Root-mean-square normalization (torch ``nn.RMSNorm`` parity;
    Zhang & Sennrich, arXiv:1910.07467) — no mean subtraction, no bias,
    the LLaMA-family default.  Statistics in f32, result in x.dtype.
    ``zero_centered`` scales by ``1 + weight`` with the weight initialised
    to zero (Qwen3-Next's norm), the same function at initialisation."""

    def __init__(self, normalized_shape, eps: float = 1e-6,
                 elementwise_affine: bool = True,
                 zero_centered: bool = False):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.normalized_shape = tuple(normalized_shape)
        self.eps = eps
        self.elementwise_affine = elementwise_affine
        self.zero_centered = zero_centered

    def create_params(self, key):
        if not self.elementwise_affine:
            return None
        fill = jnp.zeros if self.zero_centered else jnp.ones
        return {"weight": fill(self.normalized_shape)}

    def forward(self, x):
        axes = tuple(range(x.ndim - len(self.normalized_shape), x.ndim))
        w = (_ctx().get_params(self._path)["weight"]
             if self.elementwise_affine else None)
        if w is not None and self.zero_centered:
            w = 1.0 + w
        return F.rms_norm(x, w, self.eps, axes)

    def __repr__(self):
        return f"RMSNorm({self.normalized_shape})"


class GELU(Module):
    """Gaussian error linear unit (exact erf form, torch default)."""

    def forward(self, x):
        return jax.nn.gelu(x, approximate=False)

    def __repr__(self):
        return "GELU()"


class GatedMLP(Module):
    """SiLU-gated feed-forward without biases, ``down(silu(gate(x)) *
    up(x))`` (SwiGLU; the LLaMA-family MLP), ``hidden`` wide.
    ``gate_multiplier`` scales the gate's projection before the SiLU and
    ``down_multiplier`` the output (Falcon-H1's ``mlp_multipliers``); 1 is
    no operation."""

    def __init__(self, dim: int, hidden: int, gate_multiplier: float = 1.0,
                 down_multiplier: float = 1.0):
        super().__init__()
        self.gate = Linear(dim, hidden, bias=False)
        self.up = Linear(dim, hidden, bias=False)
        self.down = Linear(hidden, dim, bias=False)
        self.gate_multiplier = float(gate_multiplier)
        self.down_multiplier = float(down_multiplier)

    def forward(self, x):
        gate = F.scaled(self.gate(x), self.gate_multiplier)
        return F.scaled(self.down(jax.nn.silu(gate) * self.up(x)),
                        self.down_multiplier)

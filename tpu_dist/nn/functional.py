"""Stateless NN ops lowered straight to XLA (lax) primitives.

These are the TPU-native equivalents of the cuDNN/ATen kernels the reference
exercises through torch layers (conv/pool/relu/linear/cross-entropy at
/root/reference/mpspawn_dist.py:11-43,63).  Convolutions use NHWC/HWIO — the
layout XLA tiles best onto the TPU MXU — rather than torch's NCHW/OIHW.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "conv2d", "max_pool2d", "avg_pool2d", "relu", "linear", "dropout",
    "log_softmax", "softmax", "cross_entropy", "one_hot", "flatten",
    "batch_norm", "rms_norm", "scaled",
]

_IntOr2 = Union[int, Tuple[int, int]]


def _pair(v: _IntOr2) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def conv2d(x, w, b=None, stride: _IntOr2 = 1, padding: _IntOr2 = 0,
           dilation: _IntOr2 = 1, groups: int = 1):
    """2-D convolution, NHWC input, HWIO kernel.

    ``padding`` is symmetric-integer (torch semantics); strings "SAME"/"VALID"
    are also accepted.
    """
    sh, sw = _pair(stride)
    dh, dw = _pair(dilation)
    if isinstance(padding, str):
        pad = padding
    else:
        ph, pw = _pair(padding)
        pad = [(ph, ph), (pw, pw)]
    return _bias_add(
        lax.conv_general_dilated(
            x, w,
            window_strides=(sh, sw),
            padding=pad,
            rhs_dilation=(dh, dw),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups,
        ),
        b,
    )


def _bias_add(y, b):
    return y if b is None else y + b


def max_pool2d(x, kernel_size: _IntOr2, stride: Optional[_IntOr2] = None,
               padding: _IntOr2 = 0):
    """Max pooling over NHWC, floor mode (torch default)."""
    kh, kw = _pair(kernel_size)
    sh, sw = _pair(stride if stride is not None else kernel_size)
    ph, pw = _pair(padding)
    return lax.reduce_window(
        x, -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, sh, sw, 1),
        padding=[(0, 0), (ph, ph), (pw, pw), (0, 0)],
    )


def avg_pool2d(x, kernel_size: _IntOr2, stride: Optional[_IntOr2] = None,
               padding: _IntOr2 = 0, count_include_pad: bool = True):
    kh, kw = _pair(kernel_size)
    sh, sw = _pair(stride if stride is not None else kernel_size)
    ph, pw = _pair(padding)
    # NOTE: the init value must be a Python scalar (not an Array) so JAX
    # recognizes the add-monoid and uses the differentiable window-sum path.
    summed = lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, sh, sw, 1),
        padding=[(0, 0), (ph, ph), (pw, pw), (0, 0)],
    )
    if count_include_pad or (ph == 0 and pw == 0):
        # torch default: padded zeros count toward the denominator
        return summed / (kh * kw)
    counts = lax.reduce_window(
        jnp.ones(x.shape[:3] + (1,), x.dtype), 0.0, lax.add,
        window_dimensions=(1, kh, kw, 1),
        window_strides=(1, sh, sw, 1),
        padding=[(0, 0), (ph, ph), (pw, pw), (0, 0)],
    )
    return summed / counts


def relu(x):
    return jnp.maximum(x, 0)


def linear(x, w, b=None):
    """``x @ w + b`` with ``w`` shaped (in_features, out_features)."""
    return _bias_add(jnp.dot(x, w), b)


def scaled(x, multiplier: float):
    """``x`` times a constant of the program (a muP multiplier); 1 is no
    operation, so a model without one traces as it did."""
    return x if multiplier == 1.0 else x * multiplier


def dropout(x, rate: float, key, training: bool = True):
    """Inverted dropout: scale by 1/(1-rate) at train time, identity at eval."""
    if not training or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def one_hot(labels, num_classes: int, dtype=jnp.float32):
    return jax.nn.one_hot(labels, num_classes, dtype=dtype)


def log_softmax(x, axis: int = -1):
    return jax.nn.log_softmax(x, axis=axis)


def softmax(x, axis: int = -1):
    return jax.nn.softmax(x, axis=axis)


def cross_entropy(logits, labels, reduction: str = "mean",
                  label_smoothing: float = 0.0, ignore_index: int = -100,
                  weight=None):
    """Softmax cross-entropy with integer labels (torch CrossEntropyLoss).

    Matches ``nn.CrossEntropyLoss()`` as used at
    /root/reference/mpspawn_dist.py:63 and /root/reference/example_mp.py:83,
    including the optional torch semantics:

    - ``label_smoothing``: blend ``(1-eps)*nll + eps*mean_c(-logp_c)``;
    - ``ignore_index``: rows with this label contribute nothing (and are
      excluded from the mean's denominator), torch's padding convention;
    - ``weight``: per-class rescaling; the mean divides by the summed
      weights of the counted rows, exactly as torch does.
    """
    labels = labels.astype(jnp.int32)
    keep = labels != ignore_index
    safe = jnp.where(keep, labels, 0)  # ignored rows must not index OOB
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    wy = (jnp.asarray(weight)[safe] if weight is not None
          else jnp.ones_like(nll))
    loss = nll * wy
    if label_smoothing:
        # torch formula: the target term scales by w[y], the uniform term
        # weights each class's -logp by its own w_c (NOT by w[y])
        wc = jnp.asarray(weight) if weight is not None else 1.0
        smooth = -(logp * wc).sum(axis=-1) / logits.shape[-1]
        loss = (1.0 - label_smoothing) * loss + label_smoothing * smooth
    wy = jnp.where(keep, wy, 0.0)
    loss = jnp.where(keep, loss, 0.0)
    if reduction == "mean":
        return loss.sum() / jnp.maximum(wy.sum(), jnp.finfo(loss.dtype).tiny)
    if reduction == "sum":
        return loss.sum()
    if reduction == "none":
        return loss
    raise ValueError(f"Unknown reduction {reduction!r}")


def flatten(x, start_dim: int = 1):
    return x.reshape(x.shape[:start_dim] + (-1,))


def batch_norm(x, mean, var, weight=None, bias=None, eps: float = 1e-5):
    """Normalize NHWC (or (N, C)) activations with given statistics."""
    inv = lax.rsqrt(var + eps)
    y = (x - mean) * inv
    if weight is not None:
        y = y * weight
    if bias is not None:
        y = y + bias
    return y


def rms_norm(x, weight=None, eps: float = 1e-6, axes=(-1,)):
    """``x / rms(x)`` over ``axes`` (statistics in float32, result in
    ``x.dtype``), times ``weight`` if given — Zhang & Sennrich 2019."""
    xf = x.astype(jnp.float32)
    y = (xf * lax.rsqrt(jnp.mean(jnp.square(xf), axes, keepdims=True) + eps)
         ).astype(x.dtype)
    # cast the weight, not the product: keep the promised output dtype
    return y if weight is None else y * weight.astype(x.dtype)

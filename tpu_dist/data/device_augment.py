"""On-device (jitted) image augmentation: crop / flip / normalize as XLA ops.

Why this exists: the reference keeps the chip fed by throwing host cores at
augmentation (`num_workers=4, pin_memory=True`, /root/reference/example_mp.py:74-80).
On a TPU host with few cores that strategy fails — a round-2 lead put the
host pipeline at 169 img/s per core against a 9.5k img/s ResNet-50 step
(57 cores' worth of numpy).  The TPU-native fix is to move
the math to the chip: the host only *slices raw uint8 bytes* (cheap — a
memcpy per batch) and ships them over PCIe at uint8 width (4x fewer bytes
than f32); the crop/flip/normalize runs as one jitted XLA program on
device, where it is fused, bf16-friendly, and overlaps the train step's
dispatch queue.

Semantics match the host transforms (`transforms.py`) exactly at the
resample level — `bilinear_crop_resize` here is the same half-pixel-
centered math as `transforms._bilinear_crop_resize_numpy` (tested for
parity on identical boxes); the random *draws* use `jax.random` instead of
`numpy.random`, so a device-augmented epoch is a different (equally valid)
sample stream than a host-augmented one.

Usage::

    aug = DeviceAugment.imagenet(224)            # RandomResizedCrop+flip+norm
    aug = DeviceAugment.cifar10(32, padding=4)   # pad4+RandomCrop+flip+norm
    loader = DeviceLoader(host_loader, augment=aug)   # host yields uint8

or standalone: ``out = aug(x_uint8_on_device, key)``.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .transforms import (CIFAR10_MEAN, CIFAR10_STD, IMAGENET_MEAN,
                         IMAGENET_STD, _pair)

__all__ = ["DeviceAugment", "bilinear_crop_resize"]


def bilinear_crop_resize(x, top, left, crop_h, crop_w,
                         out_hw: Tuple[int, int]):
    """Resample per-image boxes to ``out_hw`` bilinearly (jax version of
    ``transforms._bilinear_crop_resize_numpy`` — same half-pixel-centered
    coordinates, same clamping; static output shape, traced box values).

    ``x``: (N, H, W, C) float; ``top/left/crop_h/crop_w``: (N,) float.
    Separable: interpolate rows first (take_along_axis over H), then
    columns — two gathers of full rows instead of four point-gathers,
    which XLA lowers to efficient dynamic-slice-free gathers on TPU.
    """
    x = x.astype(jnp.float32)
    n, h, w, c = x.shape
    oh, ow = out_hw
    ys = (top[:, None] + (jnp.arange(oh, dtype=jnp.float32)[None, :] + 0.5)
          * (crop_h[:, None] / oh) - 0.5)                        # (N, oh)
    xs = (left[:, None] + (jnp.arange(ow, dtype=jnp.float32)[None, :] + 0.5)
          * (crop_w[:, None] / ow) - 0.5)                        # (N, ow)
    ys = jnp.clip(ys, 0.0, h - 1.0)
    xs = jnp.clip(xs, 0.0, w - 1.0)
    y0 = jnp.floor(ys).astype(jnp.int32)
    x0 = jnp.floor(xs).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (ys - y0)[:, :, None, None]                             # (N, oh, 1, 1)
    wx = (xs - x0)[:, None, :, None]                             # (N, 1, ow, 1)

    def rows(idx):  # (N, oh) -> (N, oh, W, C)
        return jnp.take_along_axis(x, idx[:, :, None, None], axis=1)

    xrows = rows(y0) * (1 - wy) + rows(y1) * wy                  # (N, oh, W, C)

    def cols(idx):  # (N, ow) -> (N, oh, ow, C)
        return jnp.take_along_axis(xrows, idx[:, None, :, None], axis=2)

    return cols(x0) * (1 - wx) + cols(x1) * wx


class DeviceAugment:
    """Jitted on-device augmentation for raw uint8 NHWC batches.

    ``mode='resized_crop'`` — torchvision RandomResizedCrop semantics
    (area in ``scale``·A, log-uniform aspect in ``ratio``, centered
    max-box fallback for infeasible draws — transforms.py:194-226) +
    RandomHorizontalFlip + Normalize.

    ``mode='pad_crop'`` — zero-pad by ``padding`` then integer RandomCrop
    (torchvision RandomCrop(32, padding=4) semantics,
    /root/reference/example_mp.py:62) + flip + Normalize.

    Input uint8 (or float in [0,1]); output ``dtype`` (default float32;
    pass ``jnp.bfloat16`` to feed a bf16 step with no extra cast).
    Deterministic per ``key``.  The callable is jit-compiled once per
    input shape; sharded inputs stay sharded (every op is per-image, so
    XLA partitions it with zero collectives).
    """

    def __init__(self, size, mode: str = "resized_crop",
                 scale=(0.08, 1.0), ratio=(3.0 / 4.0, 4.0 / 3.0),
                 padding: int = 0, flip_p: float = 0.5,
                 resize: Optional[int] = None,
                 mean: Sequence[float] = IMAGENET_MEAN,
                 std: Sequence[float] = IMAGENET_STD,
                 dtype=jnp.float32):
        if mode not in ("resized_crop", "pad_crop", "center_crop", "none"):
            raise ValueError(f"unknown mode {mode!r}")
        self.size = _pair(size)
        self.mode = mode
        self.scale = tuple(scale)
        self.ratio = tuple(ratio)
        self.padding = int(padding)
        self.flip_p = float(flip_p)
        self.resize = resize
        self.mean = tuple(float(m) for m in mean)
        self.std = tuple(float(s) for s in std)
        self.dtype = dtype
        self._fn = jax.jit(self._build())

    @classmethod
    def imagenet(cls, size: int = 224, dtype=jnp.float32, **kw):
        return cls(size, mode="resized_crop", mean=IMAGENET_MEAN,
                   std=IMAGENET_STD, dtype=dtype, **kw)

    @classmethod
    def imagenet_eval(cls, size: int = 224, resize: int = 256,
                      dtype=jnp.float32, **kw):
        """torchvision eval pipeline ``Resize(resize)+CenterCrop(size)`` as
        ONE device resample: the short side scaled to ``resize`` then the
        central ``size``² window is a single centered crop box in the
        ORIGINAL image of short-side fraction size/resize — no intermediate
        resized image is ever materialized.  Deterministic (no random
        draws); the ``key`` argument is accepted and ignored."""
        return cls(size, mode="center_crop", resize=resize, flip_p=0.0,
                   mean=IMAGENET_MEAN, std=IMAGENET_STD, dtype=dtype, **kw)

    @classmethod
    def cifar10(cls, size: int = 32, padding: int = 4, dtype=jnp.float32,
                **kw):
        return cls(size, mode="pad_crop", padding=padding,
                   mean=CIFAR10_MEAN, std=CIFAR10_STD, dtype=dtype, **kw)

    # -- internals -------------------------------------------------------------
    def _build(self):
        oh, ow = self.size
        lo, hi = self.scale
        log_r0, log_r1 = math.log(self.ratio[0]), math.log(self.ratio[1])
        pad, flip_p = self.padding, self.flip_p
        mean = jnp.asarray(self.mean, jnp.float32)
        std = jnp.asarray(self.std, jnp.float32)
        mode, out_dtype = self.mode, self.dtype
        resize = self.resize

        # note: branches on mode/pad/flip_p resolve at TRACE time (static)
        def fn(x, key):
            n, h, w, c = x.shape
            raw_uint8 = x.dtype == jnp.uint8
            x = x.astype(jnp.float32)
            if raw_uint8:
                # raw bytes arrive unscaled; match the host loader's
                # ToTensor step (loader.py:149-150)
                x = x / 255.0
            k_area, k_ar, k_top, k_left, k_flip = jax.random.split(key, 5)
            if mode == "resized_crop":
                area = float(h * w)
                target = area * jax.random.uniform(
                    k_area, (n,), minval=lo, maxval=hi)
                aspect = jnp.exp(jax.random.uniform(
                    k_ar, (n,), minval=log_r0, maxval=log_r1))
                cw = jnp.sqrt(target * aspect)
                ch = jnp.sqrt(target / aspect)
                bad = (cw > w) | (ch > h)
                shrink = jnp.minimum(w / jnp.maximum(cw, 1e-6),
                                     h / jnp.maximum(ch, 1e-6))
                cw = jnp.where(bad, cw * shrink, cw)
                ch = jnp.where(bad, ch * shrink, ch)
                top = jax.random.uniform(k_top, (n,)) * (h - ch)
                left = jax.random.uniform(k_left, (n,)) * (w - cw)
                x = bilinear_crop_resize(x, top, left, ch, cw, (oh, ow))
            elif mode == "center_crop":
                # Resize(short side -> `resize`) + CenterCrop(oh, ow),
                # composed into one crop box in the original image: the
                # crop covers (oh/resize, ow/resize) of the short side,
                # centered (matches torchvision's eval pipeline up to its
                # two-pass resampling error)
                short = float(min(h, w))
                ch_c = short * oh / resize
                cw_c = short * ow / resize
                top = jnp.full((n,), (h - ch_c) / 2.0, jnp.float32)
                left = jnp.full((n,), (w - cw_c) / 2.0, jnp.float32)
                x = bilinear_crop_resize(x, top, left,
                                         jnp.full((n,), ch_c, jnp.float32),
                                         jnp.full((n,), cw_c, jnp.float32),
                                         (oh, ow))
            elif mode == "pad_crop":
                if pad:
                    x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
                ph, pw = h + 2 * pad, w + 2 * pad
                if oh > ph or ow > pw:
                    raise ValueError(f"crop {self.size} larger than padded "
                                     f"input ({ph}, {pw})")
                top = jax.random.randint(k_top, (n,), 0, ph - oh + 1)
                left = jax.random.randint(k_left, (n,), 0, pw - ow + 1)
                # integer crop == bilinear resample at integer coords with
                # crop size == out size (frac weights are exactly 0)
                x = bilinear_crop_resize(x, top.astype(jnp.float32),
                                         left.astype(jnp.float32),
                                         jnp.full((n,), float(oh)),
                                         jnp.full((n,), float(ow)),
                                         (oh, ow))
            if flip_p > 0:
                flipped = x[:, :, ::-1, :]
                if flip_p >= 1.0:
                    x = flipped
                else:
                    m = jax.random.uniform(k_flip, (n,)) < flip_p
                    x = jnp.where(m[:, None, None, None], flipped, x)
            x = (x - mean) / std
            return x.astype(out_dtype)

        return fn

    def __call__(self, x, key):
        """Augment a device-resident batch; ``key`` a jax PRNG key."""
        return self._fn(x, key)

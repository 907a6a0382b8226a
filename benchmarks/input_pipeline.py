"""Ladder-#5 input-pipeline benchmark: can the loader feed the chip?

Two pipelines are measured against the ResNet-50 bf16 fused-step rate:

(a) **host-augment** (the reference's strategy,
    /root/reference/example_mp.py:74-80 — numpy RandomResizedCrop + flip
    + normalize on host cores).  On a few-core TPU host this loses badly
    (round 2: 169 img/s vs a 9.5k img/s step — 57 cores' worth).

(b) **device-augment** (the TPU-native strategy, data/device_augment.py):
    the host only fancy-indexes raw uint8 bytes out of an in-RAM array
    (the decoded-cache scenario; JPEG decode is out of scope for both
    pipelines) and ships uint8 over PCIe; crop/flip/normalize runs as one
    jitted XLA program on device.  The chip then spends 1/aug + 1/step
    seconds per image; the verdict `loader_keeps_chip_fed` is
    ``raw_host_rate >= combined chip consumption rate``.

Timing on the chip uses scan-chunked min-of-reps differencing
(benchmarks/timing.py).
"""

from __future__ import annotations

import json
import os
import sys
import time


def host_augment_images_per_sec(num_workers: int, batch: int = 128,
                                n_images: int = 1024, image_size: int = 224,
                                repeats: int = 3) -> float:
    """Reference-style pipeline: full augmentation in numpy on the host."""
    from tpu_dist.data import DataLoader, SyntheticImageNet, transforms

    aug = transforms.Compose([
        transforms.RandomResizedCrop(image_size),
        transforms.RandomHorizontalFlip(),
        transforms.Normalize(transforms.IMAGENET_MEAN,
                             transforms.IMAGENET_STD),
    ])
    ds = SyntheticImageNet(train=True, n=n_images, image_size=image_size,
                           num_classes=1000, transform=aug)
    loader = DataLoader(ds, batch_size=batch, shuffle=True, drop_last=True,
                        num_workers=num_workers)
    for _ in loader:  # warm (allocators, page-in)
        break
    best = float("inf")
    for ep in range(repeats):
        loader.set_epoch(ep)
        t0 = time.perf_counter()
        seen = 0
        for x, y in loader:
            seen += len(x)
        best = min(best, (time.perf_counter() - t0) / seen)
    return 1.0 / best


def _raw_dataset(n_images: int, image_size: int):
    """Materialize the synthetic set ONCE into an in-RAM uint8 array; the
    raw path's per-batch host work is then pure fancy-index + memcpy."""
    import numpy as np
    from tpu_dist.data import ArrayImageDataset, SyntheticImageNet

    src = SyntheticImageNet(train=True, n=n_images, image_size=image_size,
                            num_classes=1000, transform=None)
    x, y = src.gather(np.arange(n_images))
    return ArrayImageDataset(x, y)


def raw_host_images_per_sec(batch: int = 128, n_images: int = 1024,
                            image_size: int = 224, repeats: int = 3) -> float:
    """Device-augment pipeline's HOST half: slice raw uint8 batches."""
    from tpu_dist.data import DataLoader

    loader = DataLoader(_raw_dataset(n_images, image_size), batch_size=batch,
                        shuffle=True, drop_last=True, to_float=False)
    for _ in loader:
        break
    best = float("inf")
    for ep in range(repeats):
        loader.set_epoch(ep)
        t0 = time.perf_counter()
        seen = 0
        for x, y in loader:
            seen += len(x)
        best = min(best, (time.perf_counter() - t0) / seen)
    return 1.0 / best


def device_augment_images_per_sec(batch: int = 128, image_size: int = 224,
                                  raw_size: int = 256, steps: int = 50,
                                  reps: int = 6) -> float:
    """Device-augment pipeline's CHIP half, scan-differenced.

    A jitted ``lax.scan`` applies the augmentation ``k`` times with a data
    dependency threaded through a scalar (so XLA cannot elide iterations);
    min-of-reps over a long-minus-short difference cancels dispatch
    overhead and contention spikes (timing.py methodology).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from tpu_dist.data import DeviceAugment

    aug = DeviceAugment.imagenet(image_size, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    x8 = jnp.asarray(rng.integers(0, 256, (batch, raw_size, raw_size, 3),
                                  np.uint8))

    def chunk(k):
        @jax.jit
        def run(x, key):
            def body(carry, i):
                out = aug(x + carry, jax.random.fold_in(key, i))
                # thread one element back as the carry (uint8 dep)
                return out[0, 0, 0, 0].astype(jnp.uint8) * 0, ()
            c, _ = lax.scan(body, jnp.uint8(0), jnp.arange(k))
            return c
        return run

    key = jax.random.key(0)
    long_k, short_k = steps, max(1, steps // 5)
    run_long, run_short = chunk(long_k), chunk(short_k)
    for f in (run_long, run_short):  # compile + warm
        f(x8, key).block_until_ready()

    def t(f):
        t0 = time.perf_counter()
        int(f(x8, key))  # readback syncs
        return time.perf_counter() - t0

    d_long = min(t(run_long) for _ in range(reps))
    d_short = min(t(run_short) for _ in range(reps))
    diff = (d_long - d_short) / (long_k - short_k)
    if diff <= 0:  # contention crossed the minima; gross long is safe
        diff = d_long / long_k
    return batch / diff


def device_step_images_per_sec(batch: int = 128,
                               image_size: int = 224) -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.models import resnet50
    from tpu_dist.parallel import DistributedDataParallel
    from .timing import ddp_repeat_step_time

    own_group = not dist.is_initialized()
    pg = dist.init_process_group() if own_group else dist.get_default_group()
    n_chips = dist.get_world_size()
    ddp = DistributedDataParallel(
        resnet50(num_classes=1000),
        optimizer=optim.SGD(lr=0.1, momentum=0.9, weight_decay=1e-4),
        loss_fn=nn.CrossEntropyLoss(), group=pg, donate=True,
        compute_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    sharding = NamedSharding(pg.mesh, P(pg.axis_name))
    x = jax.device_put(
        rng.normal(size=(batch * n_chips, image_size, image_size, 3))
        .astype(np.float32), sharding)
    y = jax.device_put(rng.integers(0, 1000, batch * n_chips).astype(np.int32),
                       sharding)

    t = ddp_repeat_step_time(ddp, x, y, steps=20, reps=3)
    if own_group:
        dist.destroy_process_group()
    return batch * n_chips / t


def run(batch: int = 128, image_size: int = 224,
        raw_size: int = 256) -> dict:
    """``raw_size``: edge of the cached raw images (the ImageNet
    short-side-256 decode cache); both the raw host slice and the device
    RandomResizedCrop(224) consume this size."""
    host_aug = {w: round(host_augment_images_per_sec(
        w, batch=batch, image_size=image_size), 1) for w in (0, 4)}
    raw_host = raw_host_images_per_sec(batch=batch, image_size=raw_size)
    dev_aug = device_augment_images_per_sec(batch=batch,
                                            image_size=image_size,
                                            raw_size=raw_size)
    step = device_step_images_per_sec(batch=batch, image_size=image_size)
    # chip consumption rate with on-device augmentation: each image costs
    # 1/aug + 1/step seconds of chip time
    consume = 1.0 / (1.0 / dev_aug + 1.0 / step)
    cores = os.cpu_count() or 1
    per_core = max(host_aug[0], 1e-9)
    return {
        "metric": "imagenet_input_pipeline_vs_resnet50_step",
        "host_augment_images_per_sec": host_aug,
        "raw_host_images_per_sec": round(raw_host, 1),
        "device_augment_images_per_sec": round(dev_aug, 1),
        "resnet50_bf16_step_images_per_sec": round(step, 1),
        "chip_consume_images_per_sec": round(consume, 1),
        "loader_over_step": round(raw_host / consume, 2),
        "loader_keeps_chip_fed": raw_host >= consume,
        "host_cores": cores,
        "host_augment_cores_to_feed_estimate": int(-(-step // per_core)),
        "batch": batch,
        "image_size": image_size,
        "raw_size": raw_size,
        "note": "raw path = in-RAM uint8 slice (decoded-cache scenario); "
                "augmentation on device (data/device_augment.py)",
    }


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    print(json.dumps(run()))

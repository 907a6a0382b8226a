"""Ladder-#5 END-TO-END: sustained ResNet-50 training throughput with the
full input pipeline in the loop.

benchmarks/input_pipeline.py proves each half separately (raw host rate,
device-augment rate, step rate) and combines them analytically; this row
runs the actual production loop — host fancy-indexes raw uint8 out of an
in-RAM array, DeviceLoader ships uint8 + applies the jitted DeviceAugment,
DDP bf16 fused step consumes — and reports wall-clock images/sec over
several epochs with ONE readback at the end (async dispatch keeps the
queue full; per-step readback would serialize a host round trip into
every step).

The sustained number is the ladder-#5 capability claim: what a user
actually gets from `examples/example_imagenet.py` (same components, same
defaults).  Each batch ships ~25 MB of uint8 host to device, so the
sustained rate is bound by whichever is slowest of the host slice, that
link and the step; the per-component rates are in
imagenet_input_pipeline_vs_resnet50_step.  Not measured on this machine.
"""

from __future__ import annotations

import json
import time


def run(batch: int = 128, image_size: int = 224, raw_size: int = 256,
        n_images: int = 2048, epochs: int = 3, prefetch: int = 3) -> dict:
    # prefetch 3: three staged batches keep a slow host-to-device link
    # busy without queue blowup (the example's default too)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.data import (ArrayImageDataset, DataLoader, DeviceAugment,
                               DeviceLoader)
    from tpu_dist.models import resnet50
    from tpu_dist.parallel import DistributedDataParallel

    own_group = not dist.is_initialized()
    pg = dist.init_process_group() if own_group else dist.get_default_group()
    n_chips = dist.get_world_size()

    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, (n_images, raw_size, raw_size, 3), np.uint8)
    y = rng.integers(0, 1000, n_images).astype(np.int64)
    ds = ArrayImageDataset(x, y)
    host = DataLoader(ds, batch_size=batch * n_chips, shuffle=True,
                      drop_last=True, to_float=False)
    aug = DeviceAugment.imagenet(image_size, dtype=jnp.bfloat16)
    loader = DeviceLoader(host, group=pg, augment=aug, prefetch=prefetch)

    ddp = DistributedDataParallel(
        resnet50(num_classes=1000),
        optimizer=optim.SGD(lr=0.1, momentum=0.9, weight_decay=1e-4),
        loss_fn=nn.CrossEntropyLoss(), group=pg, donate=True,
        compute_dtype=jnp.bfloat16)
    state = ddp.init(seed=0)

    # warm epoch: compiles (augment + step) and pages the dataset in
    m = None
    for images, labels in loader:
        state, m = ddp.train_step(state, images, labels)
    float(m["loss"])

    t0 = time.perf_counter()
    steps = 0
    for ep in range(1, epochs + 1):
        loader.set_epoch(ep)
        for images, labels in loader:
            state, m = ddp.train_step(state, images, labels)
            steps += 1
    float(m["loss"])  # single sync: drain the dispatch queue
    wall = time.perf_counter() - t0
    imgs = steps * batch * n_chips

    if own_group:
        dist.destroy_process_group()
    return {
        "metric": "resnet50_imagenet_e2e_sustained_images_per_sec",
        "value": round(imgs / wall, 1),
        "unit": "images/sec (end-to-end, host loader in the loop)",
        "steps": steps,
        "wall_sec": round(wall, 2),
        "per_chip_batch": batch,
        "image_size": image_size,
        "raw_size": raw_size,
        "n_chips": n_chips,
        "pipeline": f"raw uint8 slice -> DeviceLoader(prefetch={prefetch}) "
                    "-> DeviceAugment (jitted, bf16) -> DDP bf16 fused step",
        "transfer_bytes_per_batch": batch * n_chips * raw_size ** 2 * 3,
        "note": "bound by the slowest of host slice, host->device link "
                "and step; per-component rates in "
                "imagenet_input_pipeline_vs_resnet50_step",
    }


if __name__ == "__main__":
    print(json.dumps(run()))

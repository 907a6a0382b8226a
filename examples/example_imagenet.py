"""ImageNet-class ResNet-50 data-parallel training — BASELINE.md ladder #5
(ResNet-50 ImageNet-1k DDP on a pod slice), the scaled-up form of the
reference's CIFAR script (/root/reference/example_mp.py:50,74-90).

Workload shape: ResNet-50, 224x224x3 inputs, 1000 classes, per-replica batch
128, SGD lr 0.1 (linear-scaling rule base), momentum .9, wd 1e-4; mixed
precision (bf16 compute, f32 master weights) on by default — the TPU recipe.
Input pipeline: RandomResizedCrop(224) + HorizontalFlip + Normalize —
by default as ONE jitted XLA program on device (data/device_augment.py;
the host only slices raw uint8, the sole way a few-core TPU host keeps a
ResNet-50 fed), double-buffered onto the mesh through DeviceLoader.
``--host-augment`` restores the reference's numpy-on-host-workers recipe
(/root/reference/example_mp.py:74-80 idiom).

Data: ``--imagefolder PATH`` trains from an on-disk
``root/<class>/<img>`` tree (real ImageNet layout); default is the
deterministic SyntheticImageNet stand-in, which keeps the example hermetic
in egress-less environments.

``--model vit_b_16`` swaps the trunk for the torchvision-parity ViT-B/16
(models/vit.py) with its AdamW recipe — same sampler, augmentation, and
DDP step; the attention era rides the identical pipeline.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))  # run as a script without install
from datetime import datetime


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--dist-url", default=None, type=str)
    parser.add_argument("--nodes", default=1, type=int)
    parser.add_argument("--node_rank", default=0, type=int)
    parser.add_argument("--epochs", default=1, type=int)
    parser.add_argument("--batch-size", default=128, type=int,
                        help="per-replica batch")
    parser.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    parser.add_argument("--imagefolder", default=None, type=str,
                        help="ImageFolder root (default: synthetic ImageNet)")
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet50", "vit_b_16"],
                        help="resnet50 (SGD .1/.9/1e-4, the ladder recipe) "
                             "or vit_b_16 (AdamW 3e-4/wd .05 — SGD "
                             "diverges ViT from scratch)")
    parser.add_argument("--image-size", default=224, type=int)
    parser.add_argument("--num-classes", default=1000, type=int)
    parser.add_argument("--synthetic-size", default=2048, type=int)
    parser.add_argument("--num-workers", default=4, type=int)
    parser.add_argument("--host-augment", action="store_true",
                        help="torchvision-style numpy augmentation on host "
                             "workers (the reference recipe). Default is "
                             "on-DEVICE augmentation: the host ships raw "
                             "uint8 and crop/flip/normalize runs as one "
                             "jitted XLA program — the only way a few-core "
                             "TPU host feeds a ResNet-50")
    parser.add_argument("--no-bf16", action="store_true",
                        help="full f32 compute (default is mixed bf16)")
    parser.add_argument("--sync-bn", action="store_true")
    parser.add_argument("--max-steps", default=0, type=int)
    parser.add_argument("--evaluate", action="store_true",
                        help="held-out evaluation after training "
                             "(Resize+CenterCrop eval pipeline; on-device "
                             "by default, host under --host-augment)")
    parser.add_argument("--local_rank", default=None, type=int,
                        help="accepted for the classic launcher argv form")
    args = parser.parse_args()

    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"   # before the first jax import
    import jax.numpy as jnp
    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.data import (DataLoader, DeviceLoader, DistributedSampler,
                               ImageFolder, SyntheticImageNet, transforms)
    from tpu_dist.models import resnet50, vit_b_16
    from tpu_dist.parallel import DistributedDataParallel

    init_method = args.dist_url
    if init_method is None and "MASTER_ADDR" in os.environ:
        init_method = "env://"
    kw = {}
    if init_method and init_method.startswith("tcp://"):
        kw = dict(world_size=args.nodes, rank=args.node_rank)
    pg = dist.init_process_group(backend=args.backend,
                                 init_method=init_method, **kw)
    rank = dist.get_rank()
    print(f"[init] == process rank {rank}, "
          f"{dist.get_world_size()} device replicas ==")

    host_aug = None
    if args.host_augment:
        host_aug = transforms.Compose([
            transforms.RandomResizedCrop(args.image_size),
            transforms.RandomHorizontalFlip(),
            transforms.Normalize(transforms.IMAGENET_MEAN,
                                 transforms.IMAGENET_STD),
        ])
    if args.imagefolder:
        ds = ImageFolder(args.imagefolder, transform=host_aug,
                         sample_size=(args.image_size + 32,
                                      args.image_size + 32))
        num_classes = len(ds.classes)
    else:
        ds = SyntheticImageNet(train=True, n=args.synthetic_size,
                               image_size=args.image_size,
                               num_classes=args.num_classes,
                               transform=host_aug)
        num_classes = args.num_classes

    if args.model == "vit_b_16":
        if args.image_size % 16:
            parser.error("--model vit_b_16 needs --image-size divisible "
                         "by 16")
        model = vit_b_16(num_classes=num_classes,
                         image_size=args.image_size)
        optimizer = optim.AdamW(lr=3e-4, weight_decay=0.05)
    else:
        model = resnet50(num_classes=num_classes)
        optimizer = optim.SGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    ddp = DistributedDataParallel(
        model, optimizer=optimizer,
        loss_fn=nn.CrossEntropyLoss(), group=pg,
        sync_batchnorm=args.sync_bn,
        compute_dtype=None if args.no_bf16 else jnp.bfloat16)
    state = ddp.init(seed=0)

    world_batch = args.batch_size * dist.get_world_size()
    sampler = DistributedSampler(ds, num_replicas=dist.get_num_processes(),
                                 rank=rank, shuffle=True)
    dev_aug = None
    if not args.host_augment:
        from tpu_dist.data import DeviceAugment
        dev_aug = DeviceAugment.imagenet(
            args.image_size,
            dtype=jnp.float32 if args.no_bf16 else jnp.bfloat16)
    # prefetch 3: three staged batches keep a slow host-to-device link
    # busy at negligible HBM cost
    loader = DeviceLoader(
        DataLoader(ds, batch_size=world_batch // dist.get_num_processes(),
                   sampler=sampler, drop_last=True,
                   num_workers=args.num_workers,
                   to_float=args.host_augment),
        group=pg, augment=dev_aug, prefetch=3)

    total_step = len(loader.loader)
    start = datetime.now()
    steps = 0
    for ep in range(args.epochs):
        sampler.set_epoch(ep)
        loader.set_epoch(ep)
        running_loss, running_correct, seen = 0.0, 0, 0
        for i, (images, labels) in enumerate(loader):
            state, metrics = ddp.train_step(state, images, labels)
            steps += 1
            running_loss += float(metrics["loss"])
            running_correct += int(metrics["correct"])
            seen += world_batch
            if (i + 1) % 10 == 0 and rank == 0:
                print("[{}] Epoch [{}/{}], Step [{}/{}], "
                      "loss: {:.3f}, acc: {:.3f}".format(
                          datetime.now().strftime("%H:%M:%S"), ep + 1,
                          args.epochs, i + 1, total_step,
                          running_loss / (i + 1), running_correct / seen))
            if args.max_steps and steps >= args.max_steps:
                break
        if args.max_steps and steps >= args.max_steps:
            break
    if rank == 0:
        print("Training complete in:", datetime.now() - start)

    if args.evaluate:
        # held-out eval through the torchvision pipeline (Resize 256 +
        # CenterCrop 224 + Normalize) — on device as one resample
        # (DeviceAugment.imagenet_eval) in the default mode, on host
        # workers under --host-augment
        from tpu_dist.data import DeviceAugment
        if args.imagefolder:
            ev_ds = ImageFolder(args.imagefolder,
                                sample_size=(args.image_size + 32,
                                             args.image_size + 32))
        else:
            ev_ds = SyntheticImageNet(train=False,
                                      n=max(args.synthetic_size // 4, 64),
                                      image_size=args.image_size,
                                      num_classes=args.num_classes)
        ev_aug = None
        if args.host_augment:
            ev_ds.transform = transforms.Compose([
                transforms.Resize(args.image_size + 32),
                transforms.CenterCrop(args.image_size),
                transforms.Normalize(transforms.IMAGENET_MEAN,
                                     transforms.IMAGENET_STD)])
        else:
            # f32 out: ddp.evaluate runs the f32 master params (no
            # compute-dtype cast on the eval path)
            ev_aug = DeviceAugment.imagenet_eval(
                args.image_size, resize=args.image_size + 32)
        ev_loader = DeviceLoader(
            DataLoader(ev_ds, batch_size=world_batch, drop_last=False,
                       num_workers=args.num_workers,
                       to_float=args.host_augment),
            group=pg, local_shards=False, augment=ev_aug)
        res = ddp.evaluate(state, ev_loader)
        if rank == 0:
            print("Eval: loss {:.3f}, acc {:.3f} ({} samples)".format(
                res["loss"], res["accuracy"], res["count"]))
    dist.destroy_process_group()


if __name__ == "__main__":
    main()

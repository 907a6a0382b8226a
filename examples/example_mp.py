"""CIFAR-10 ResNet-18 data-parallel training — TPU port of the reference's
mp.spawn CIFAR script (/root/reference/example_mp.py).

Parity points: BATCH_SIZE=256/replica, EPOCHS=5 (ref :11-12); ``--dist-url
tcp://...`` rendezvous (ref :18, :37-42); resnet18 num_classes=10 (ref :50);
RandomCrop(32,4)+HorizontalFlip augmentation with the reference's
normalization constants (ref :60-69); DistributedSampler(shuffle=True) with
``set_epoch`` per epoch (ref :73, :100); SGD lr=0.01*2, momentum .9,
wd 1e-4, nesterov (ref :84-90); global-rank-0 logs every 25 steps with
running loss + top-1 accuracy (ref :111-127).

TPU-idiomatic: one process per host, replicas = all cores; BatchNorm is
per-replica (exact DDP semantics; pass --sync-bn for cross-replica stats).
No manual seed is needed for parameter alignment (ref relies on DDP's rank-0
broadcast) — deterministic seeded init gives the same guarantee.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))  # run as a script without install
from datetime import datetime
from urllib.parse import urlparse

BATCH_SIZE = 256
EPOCHS = 5


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--nodes", default=1, type=int)
    parser.add_argument("--ngpus_per_node", default=0, type=int,
                        help="cores per node; 0 = all local devices")
    parser.add_argument("--dist-url", default=None, type=str,
                        help="tcp://host:port rendezvous (multi-host)")
    parser.add_argument("--node_rank", default=0, type=int)
    parser.add_argument("--epochs", default=EPOCHS, type=int)
    parser.add_argument("--batch-size", default=BATCH_SIZE, type=int)
    parser.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    parser.add_argument("--data-root", default="./data")
    parser.add_argument("--synthetic", action="store_true")
    parser.add_argument("--sync-bn", action="store_true")
    parser.add_argument("--max-steps", default=0, type=int)
    parser.add_argument("--bf16", action="store_true",
                        help="bfloat16 compute (BASELINE.md ladder #4)")
    parser.add_argument("--evaluate", action="store_true",
                        help="run test-set evaluation after training")
    parser.add_argument("--checkpoint-dir", default=None, type=str,
                        help="save TrainState checkpoints here")
    def _positive(v):
        v = int(v)
        if v < 1:
            raise argparse.ArgumentTypeError("must be >= 1")
        return v

    parser.add_argument("--checkpoint-every", default=100, type=_positive,
                        help="steps between checkpoints")
    parser.add_argument("--resume", action="store_true",
                        help="resume from the latest checkpoint in "
                             "--checkpoint-dir")
    args = parser.parse_args()

    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"   # before the first jax import

    import jax
    import tpu_dist.dist as dist
    from tpu_dist import checkpoint, nn, optim
    from tpu_dist.data import (CIFAR10, DataLoader, DeviceLoader,
                               DistributedSampler, transforms)
    from tpu_dist.models import resnet18
    from tpu_dist.parallel import DistributedDataParallel

    init_method = args.dist_url  # tcp://… (ref style) or None/env
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

    model = resnet18(num_classes=10)
    compute_dtype = None
    if args.bf16:
        import jax.numpy as jnp
        # mixed precision the TPU way: bf16 forward/backward on the MXU,
        # float32 master params + optimizer state (casting the params
        # themselves would be undone by the first f32 update)
        compute_dtype = jnp.bfloat16
    ddp = DistributedDataParallel(
        model,
        optimizer=optim.SGD(lr=0.01 * 2, momentum=0.9, weight_decay=1e-4,
                            nesterov=True),
        loss_fn=nn.CrossEntropyLoss(), group=pg,
        sync_batchnorm=args.sync_bn, compute_dtype=compute_dtype)
    state = ddp.init(seed=0)

    if args.resume:
        if not args.checkpoint_dir:
            raise SystemExit("--resume requires --checkpoint-dir")
        # every process must take the SAME restore-or-fresh branch (restore
        # of sharded state is collective): process 0 decides, the decision
        # is broadcast.  Non-shared checkpoint dirs then fail loudly on
        # non-zero processes instead of silently diverging.
        from tpu_dist import collectives
        last = None
        if dist.get_num_processes() == 1 or jax.process_index() == 0:
            last = checkpoint.latest_step(args.checkpoint_dir)
        if dist.get_num_processes() > 1:
            (last,) = collectives.broadcast_object_list([last], src=0,
                                                        group=pg)
        if last is None:
            if rank == 0:
                print(f"no checkpoint under {args.checkpoint_dir}; "
                      f"starting fresh")
        else:
            state = checkpoint.restore(args.checkpoint_dir, state,
                                       step=last,
                                       sharding=ddp.state_shardings(state))
            if rank == 0:
                print(f"resumed from step {last}")

    aug = transforms.Compose([
        transforms.RandomCrop(32, padding=4),
        transforms.RandomHorizontalFlip(),
        transforms.Normalize(transforms.CIFAR10_MEAN, transforms.CIFAR10_STD),
    ])
    ds = CIFAR10(root=args.data_root, train=True, transform=aug,
                 synthetic_fallback=args.synthetic or None)
    world_batch = args.batch_size * dist.get_world_size()
    sampler = DistributedSampler(ds, num_replicas=dist.get_num_processes(),
                                 rank=rank, shuffle=True)
    loader = DeviceLoader(
        DataLoader(ds, batch_size=world_batch // dist.get_num_processes(),
                   sampler=sampler, drop_last=True, num_workers=4,
                   pin_memory=True),
        group=pg)

    total_step = len(loader.loader)
    start = datetime.now()
    steps = 0
    last_saved = -1
    for ep in range(args.epochs):
        sampler.set_epoch(ep)  # epoch-seeded reshuffle (ref :100)
        running_loss, running_correct, seen = 0.0, 0, 0
        for i, (images, labels) in enumerate(loader):
            state, metrics = ddp.train_step(state, images, labels)
            steps += 1
            running_loss += float(metrics["loss"])
            running_correct += int(metrics["correct"])
            seen += world_batch
            if (i + 1) % 25 == 0 and rank == 0:
                print("[{}] Epoch [{}/{}], Step [{}/{}], "
                      "loss: {:.3f}, acc: {:.3f}".format(
                          datetime.now().strftime("%H:%M:%S"),
                          ep + 1, args.epochs, i + 1, total_step,
                          running_loss / 25, running_correct / max(seen, 1)))
                running_loss, running_correct, seen = 0.0, 0, 0
            if args.checkpoint_dir and steps % args.checkpoint_every == 0:
                last_saved = int(state.step)
                checkpoint.save(args.checkpoint_dir, state, step=last_saved,
                                keep=3)
            if args.max_steps and steps >= args.max_steps:
                break
        if args.max_steps and steps >= args.max_steps:
            break
    if args.checkpoint_dir and int(state.step) != last_saved:
        checkpoint.save(args.checkpoint_dir, state, step=int(state.step),
                        keep=3)
    if rank == 0:
        print("Training complete in: " + str(datetime.now() - start))

    if args.evaluate:
        test_ds = CIFAR10(
            root=args.data_root, train=False,
            transform=transforms.Normalize(transforms.CIFAR10_MEAN,
                                           transforms.CIFAR10_STD),
            synthetic_fallback=args.synthetic or None)
        # every process stages the SAME sequential global batches (the
        # DeviceLoader shards each over the mesh), so evaluation covers the
        # test set exactly once: no DistributedSampler padding duplicates,
        # exact count; ddp.evaluate pads the final partial batch
        test_loader = DeviceLoader(
            DataLoader(test_ds, batch_size=world_batch, drop_last=False,
                       num_workers=4, pin_memory=True),
            group=pg, local_shards=False)
        res = ddp.evaluate(state, test_loader)
        if rank == 0:
            print("Test: loss {:.3f}, acc {:.3f} ({} samples)".format(
                res["loss"], res["accuracy"], res["count"]))
    dist.destroy_process_group()


if __name__ == "__main__":
    main()

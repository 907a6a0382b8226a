"""CIFAR-10 ResNet-18 via the launch CLI — TPU port of the reference's
launcher-driven CIFAR script (/root/reference/example_launch.py).

Same workload as examples/example_mp.py with BATCH_SIZE=128/replica
(ref :10) and env-var rendezvous (ref :17-20)::

    python -m tpu_dist.launch --nproc_per_node=1 --nnodes=2 --node_rank=0 \
        --master_addr=HOST --master_port=22222 examples/example_launch.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))  # run as a script without install
from datetime import datetime

BATCH_SIZE = 128
EPOCHS = 5


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", default=EPOCHS, type=int)
    parser.add_argument("--batch-size", default=BATCH_SIZE, type=int)
    parser.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    parser.add_argument("--data-root", default="./data")
    parser.add_argument("--synthetic", action="store_true")
    parser.add_argument("--local_rank", default=None, type=int,
                        help="accepted for the classic launcher argv "
                             "contract (--pass_local_rank); env LOCAL_RANK "
                             "is authoritative")
    parser.add_argument("--sync-bn", action="store_true")
    parser.add_argument("--max-steps", default=0, type=int)
    parser.add_argument("--evaluate", action="store_true",
                        help="run test-set evaluation after training")
    args = parser.parse_args()

    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"   # before the first jax import

    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.data import (CIFAR10, DataLoader, DeviceLoader,
                               DistributedSampler, transforms)
    from tpu_dist.models import resnet18
    from tpu_dist.parallel import DistributedDataParallel

    pg = dist.init_process_group(backend=args.backend, init_method="env://"
                                 if "MASTER_ADDR" in os.environ else None)
    rank = dist.get_rank()
    print(f"[init] == local rank {dist.get_local_rank()} "
          f"(global {rank}), {dist.get_world_size()} device replicas ==")

    model = resnet18(num_classes=10)
    ddp = DistributedDataParallel(
        model,
        optimizer=optim.SGD(lr=0.01 * 2, momentum=0.9, weight_decay=1e-4,
                            nesterov=True),
        loss_fn=nn.CrossEntropyLoss(), group=pg,
        sync_batchnorm=args.sync_bn)
    state = ddp.init(seed=0)

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
    for ep in range(args.epochs):
        sampler.set_epoch(ep)
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
            if args.max_steps and steps >= args.max_steps:
                break
        if args.max_steps and steps >= args.max_steps:
            break
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

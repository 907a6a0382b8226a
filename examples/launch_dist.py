"""MNIST ConvNet via the launch CLI — TPU port of the reference's
launcher-driven script (/root/reference/launch_dist.py).

Consumes the launcher env contract (RANK/LOCAL_RANK read at
/root/reference/launch_dist.py:45-46; here via ``init_method='env://'``)::

    python -m tpu_dist.launch --nproc_per_node=1 --nnodes=2 --node_rank=0 \
        --master_addr=HOST --master_port=22222 examples/launch_dist.py

Hyperparameters match the reference: batch 100/replica, SGD lr=1e-4, seed 0,
hardcoded 10 epochs (/root/reference/launch_dist.py:79), log every 100 steps.

The reference's sampler bug — ``rank=local_rank`` instead of the global rank
(/root/reference/launch_dist.py:70), duplicating shards across nodes — is
fixed here (global process rank), per SURVEY.md §7 faithfulness notes.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))  # run as a script without install
from datetime import datetime


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", default=10, type=int)  # ref hardcodes 10
    parser.add_argument("--batch-size", default=100, type=int)
    parser.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    parser.add_argument("--data-root", default="./data")
    parser.add_argument("--synthetic", action="store_true")
    parser.add_argument("--local_rank", default=None, type=int,
                        help="accepted for the classic launcher argv "
                             "contract (--pass_local_rank); env LOCAL_RANK "
                             "is authoritative")
    parser.add_argument("--max-steps", default=0, type=int)
    parser.add_argument("--evaluate", action="store_true",
                        help="run test-set evaluation after training")
    args = parser.parse_args()

    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"   # before the first jax import

    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.data import (DataLoader, DeviceLoader, DistributedSampler,
                               MNIST, transforms)
    from tpu_dist.models import ConvNet
    from tpu_dist.parallel import DistributedDataParallel

    # env:// rendezvous — the launcher provides MASTER_ADDR/PORT/RANK/WORLD_SIZE
    pg = dist.init_process_group(backend=args.backend, init_method="env://"
                                 if "MASTER_ADDR" in os.environ else None)
    rank = dist.get_rank()
    local_rank = dist.get_local_rank()
    print(f"rank {rank} (local_rank {local_rank}) up; "
          f"{dist.get_world_size()} device replicas")

    model = ConvNet()
    ddp = DistributedDataParallel(model, optimizer=optim.SGD(lr=1e-4),
                                  loss_fn=nn.CrossEntropyLoss(), group=pg)
    state = ddp.init(seed=0)

    ds = MNIST(root=args.data_root, train=True,
               transform=transforms.Normalize(transforms.MNIST_MEAN,
                                              transforms.MNIST_STD),
               synthetic_fallback=args.synthetic or None)
    world_batch = args.batch_size * dist.get_world_size()
    sampler = DistributedSampler(ds, num_replicas=dist.get_num_processes(),
                                 rank=rank,  # GLOBAL rank (ref bug fixed)
                                 shuffle=False)
    loader = DeviceLoader(
        DataLoader(ds, batch_size=world_batch // dist.get_num_processes(),
                   sampler=sampler, drop_last=True, num_workers=2),
        group=pg)

    total_step = len(loader.loader)
    start = datetime.now()
    steps = 0
    for epoch in range(args.epochs):
        for i, (images, labels) in enumerate(loader):
            state, metrics = ddp.train_step(state, images, labels)
            steps += 1
            if (i + 1) % 100 == 0 and local_rank == 0:
                print("Epoch [{}/{}], Step [{}/{}], Loss: {:.4f}".format(
                    epoch + 1, args.epochs, i + 1, total_step,
                    float(metrics["loss"])))
            if args.max_steps and steps >= args.max_steps:
                break
        if args.max_steps and steps >= args.max_steps:
            break
    if rank == 0:
        print("Training complete in: " + str(datetime.now() - start))

    if args.evaluate:
        test_ds = MNIST(root=args.data_root, train=False,
                        transform=transforms.Normalize(
                            transforms.MNIST_MEAN, transforms.MNIST_STD),
                        synthetic_fallback=args.synthetic or None)
        # sequential full-set global batches on every process: exact
        # count, no sampler padding duplicates (see examples/example_mp.py)
        test_loader = DeviceLoader(
            DataLoader(test_ds, batch_size=world_batch, drop_last=False,
                       num_workers=2),
            group=pg, local_shards=False)
        res = ddp.evaluate(state, test_loader)
        if rank == 0:
            print("Test: loss {:.3f}, acc {:.3f} ({} samples)".format(
                res["loss"], res["accuracy"], res["count"]))
    dist.destroy_process_group()


if __name__ == "__main__":
    main()

"""MNIST ConvNet data-parallel training — TPU port of the reference's
mp.spawn script (/root/reference/mpspawn_dist.py).

Same CLI contract (-n/--nodes, -g/--gpus, -nr, --epochs), hyperparameters
(batch 100/replica, SGD lr=1e-4, seed 0) and rank-0 logging cadence (every
100 steps) — but TPU-idiomatic bring-up: ONE process per host drives all
local cores through the mesh; what the reference expresses as `mp.spawn` of
``-g`` single-GPU workers is here ``world = jax.device_count()`` replicas in
a single SPMD program (the spawn happens inside XLA, not the OS).

Run single-host (8 cores, the reference's one-node scenario)::

    python examples/mpspawn_dist.py -n 1 -g 8 --epochs 2

Multi-host: one invocation per host with MASTER_ADDR/PORT env set (or use
``python -m tpu_dist.launch --nproc_per_node=1 --nnodes=N ...``).

``--backend cpu --spawn`` reproduces the literal reference topology
(``-g`` OS processes × 1 device) for teaching parity on CPU.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))  # run as a script without install
from datetime import datetime


def train(args):
    import jax
    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.data import (DataLoader, DeviceLoader, DistributedSampler,
                               MNIST, transforms)
    from tpu_dist.models import ConvNet
    from tpu_dist.parallel import DistributedDataParallel

    init_method = "env://" if "MASTER_ADDR" in os.environ else None
    pg = dist.init_process_group(backend=args.backend,
                                 init_method=init_method)
    rank = dist.get_rank()
    world = dist.get_world_size()  # device replicas (ref: gpus × nodes)
    if rank == 0:
        print(f"My rank is {rank} of {dist.get_num_processes()} processes; "
              f"{world} device replicas")

    model = ConvNet()
    ddp = DistributedDataParallel(
        model, optimizer=optim.SGD(lr=1e-4),
        loss_fn=nn.CrossEntropyLoss(), group=pg)
    state = ddp.init(seed=0)  # == torch.manual_seed(0) on every rank
    if rank == 0:
        print("load model sucessfully!" if args.ref_logs
              else "model ready (replicated over mesh)")

    ds = MNIST(root=args.data_root, train=True,
               transform=transforms.Normalize(transforms.MNIST_MEAN,
                                              transforms.MNIST_STD),
               synthetic_fallback=args.synthetic or None)
    # batch 100 per replica (ref: per-GPU batch 100)
    global_batch = args.batch_size * world
    sampler = DistributedSampler(ds, num_replicas=dist.get_num_processes(),
                                 rank=rank, shuffle=False)
    loader = DeviceLoader(
        DataLoader(ds, batch_size=global_batch // dist.get_num_processes(),
                   sampler=sampler, drop_last=True, num_workers=2),
        group=pg, prefetch=2)
    if rank == 0:
        print("Load data....done!")

    total_step = len(loader.loader)
    start = datetime.now()
    steps = 0
    for epoch in range(args.epochs):
        # (the reference MNIST script omits set_epoch — sampler is unshuffled
        # here too, so this is a no-op kept for the correct pattern)
        loader.set_epoch(epoch)
        for i, (images, labels) in enumerate(loader):
            state, metrics = ddp.train_step(state, images, labels)
            steps += 1
            if (i + 1) % 100 == 0 and rank == 0:
                print("Epoch [{}/{}], Step [{}/{}], Loss: {:.4f}".format(
                    epoch + 1, args.epochs, i + 1, total_step,
                    float(metrics["loss"])))
            if args.max_steps and steps >= args.max_steps:
                break
        if args.max_steps and steps >= args.max_steps:
            break
    if rank == 0:
        print("Training complete in: " + str(datetime.now() - start))

    if getattr(args, "evaluate", False):
        test_ds = MNIST(root=args.data_root, train=False,
                        transform=transforms.Normalize(
                            transforms.MNIST_MEAN, transforms.MNIST_STD),
                        synthetic_fallback=args.synthetic or None)
        # sequential full-set global batches on every process: exact
        # count, no sampler padding duplicates (see examples/example_mp.py)
        test_loader = DeviceLoader(
            DataLoader(test_ds, batch_size=global_batch, drop_last=False,
                       num_workers=2),
            group=pg, local_shards=False)
        res = ddp.evaluate(state, test_loader)
        if rank == 0:
            print("Test: loss {:.3f}, acc {:.3f} ({} samples)".format(
                res["loss"], res["accuracy"], res["count"]))
    dist.destroy_process_group()


def _spawn_worker(local_rank, args):
    # teaching-parity path: one process per device on the CPU backend
    os.environ.setdefault("MASTER_ADDR", "127.0.0.1")
    os.environ.setdefault("MASTER_PORT", "29501")
    os.environ["RANK"] = str(args.nr * args.gpus + local_rank)
    os.environ["WORLD_SIZE"] = str(args.gpus * args.nodes)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    train(args)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-n", "--nodes", default=1, type=int, metavar="N")
    parser.add_argument("-g", "--gpus", default=0, type=int,
                        help="cores per node; 0 = all local devices")
    parser.add_argument("-nr", "--nr", default=0, type=int,
                        help="ranking within the nodes")
    parser.add_argument("--epochs", default=2, type=int, metavar="N")
    parser.add_argument("--batch-size", default=100, type=int,
                        help="per-replica batch (ref: 100)")
    parser.add_argument("--backend", default="tpu",
                        choices=["tpu", "cpu"])
    parser.add_argument("--spawn", action="store_true",
                        help="literal one-process-per-device mode (cpu only)")
    parser.add_argument("--data-root", default="./data")
    parser.add_argument("--synthetic", action="store_true",
                        help="use the deterministic synthetic MNIST")
    parser.add_argument("--max-steps", default=0, type=int)
    parser.add_argument("--evaluate", action="store_true",
                        help="run test-set evaluation after training")
    parser.add_argument("--ref-logs", action="store_true",
                        help="emit the reference's exact breadcrumb strings")
    args = parser.parse_args()

    if args.backend == "cpu":
        # before the first jax import, here and in every spawned child
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.spawn:
        if args.backend != "cpu":
            raise SystemExit("--spawn requires --backend cpu (TPU cores "
                             "belong to one process; see module docstring)")
        from tpu_dist.launch import spawn
        spawn(_spawn_worker, args=(args,), nprocs=args.gpus or 1)
    else:
        train(args)


if __name__ == "__main__":
    main()

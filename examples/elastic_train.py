"""Elastic MNIST training — survives preemption, crashes, and hung ranks.

The restartable version of examples/launch_dist.py: wraps the loop in
:class:`tpu_dist.resilience.TrainState` so a killed worker costs at most
``--save-every`` steps of recompute.  Run under the supervising launcher::

    python -m tpu_dist.launch --nproc_per_node=2 --master_port=0 \
        --max_restarts=3 --heartbeat_timeout=30 \
        examples/elastic_train.py --backend cpu --synthetic --max-steps 50

Kill a worker mid-run, or inject a deterministic fault::

    TPU_DIST_CHAOS="kill:rank=1,step=20" python -m tpu_dist.launch \
        --nproc_per_node=2 --master_port=0 --max_restarts=1 \
        examples/elastic_train.py --backend cpu --synthetic --max-steps 50

and watch the supervisor tear the gang down, fence the old generation,
relaunch, and resume from the latest checkpoint with an identical loss
trajectory (batches are keyed on the global step).  See docs/resilience.md
for the failure model.

With ``--elastic_world=MIN:MAX`` the world size itself is elastic: a rank
that is preempted *for good* makes the supervisor re-form the gang at the
surviving rank count (instead of burning restarts waiting for the dead),
resharding the checkpoints to the new world on resume.  Simulate the full
shrink/grow cycle deterministically::

    TPU_DIST_CHAOS="shrink:rank=1,step=20;grow:rank=0,step=35,world=2" \\
        python -m tpu_dist.launch --nproc_per_node=2 --master_port=0 \\
        --elastic_world=1:2 --heartbeat_timeout=30 \\
        examples/elastic_train.py --backend cpu --synthetic --zero \\
        --max-steps 50 --exit-on-preempt

``--exit-on-preempt`` is the production half of the same protocol: on
SIGTERM (the cloud preemption notice) the loop saves at the next step
boundary and exits ``PREEMPTED_EXIT_CODE`` so the supervisor shrinks
instead of retrying a world that can never fill.

Gradient averaging uses the bucketed ASYNC host collectives
(:class:`tpu_dist.collectives.Bucketer`): gradient leaves coalesce into
flat buckets issued as asynchronous ring all-reduces over the p2p data
plane, so the host work between issue and ``wait_all`` overlaps the sync —
and it works on any backend, including CPU test rigs where XLA has no
multiprocess computations.  On real TPU slices prefer the fused in-step
all-reduce (`tpu_dist.parallel.DistributedDataParallel`).

``--zero`` switches the update to ZeRO-1/2
(:class:`tpu_dist.parallel.ZeroOptimizer`, docs/zero.md): gradients stop
at the reduce-scatter phase, each rank keeps optimizer state only for the
chunks it owns (state memory / world), and the updated parameters come
back through an async all-gather waited lazily — the next step's batch
assembly runs under the wire.  Checkpoints then store each rank's
optimizer shard separately — world-size-portable: a run checkpointed at
one ``--nproc_per_node`` resumes at another through elastic resharding
(docs/resilience.md).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))  # run as a script without install


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", default=100, type=int)
    parser.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    parser.add_argument("--synthetic", action="store_true")
    parser.add_argument("--max-steps", default=100, type=int)
    parser.add_argument("--lr", default=0.01, type=float)
    parser.add_argument("--ckpt-root", default="./ckpt_elastic")
    parser.add_argument("--save-every", default=25, type=int)
    parser.add_argument("--zero", action="store_true",
                        help="ZeRO-1/2: reduce-scatter grads, shard the "
                             "optimizer state/update, overlap the param "
                             "all-gather")
    parser.add_argument("--exit-on-preempt", action="store_true",
                        help="on SIGTERM (cloud preemption notice): save "
                             "at the next step boundary and exit "
                             "PREEMPTED_EXIT_CODE (117) so a supervisor "
                             "running --elastic_world re-forms the gang "
                             "at the surviving rank count")
    args = parser.parse_args()

    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"   # before the first jax import
    import jax
    import numpy as np

    import tpu_dist.dist as dist
    from tpu_dist import collectives as C
    from tpu_dist import optim, resilience
    from tpu_dist.data import synthetic_mnist_arrays
    from tpu_dist.models import ConvNet
    from tpu_dist.nn import functional as F
    from tpu_dist.utils import MetricLogger, rank_zero_print

    pg = dist.init_process_group(backend=args.backend, init_method="env://"
                                 if "MASTER_ADDR" in os.environ else None)
    rank, nproc = dist.get_rank(), dist.get_num_processes()
    rank_zero_print(f"[elastic] generation {dist.generation()}, "
                    f"{nproc} processes")

    model = ConvNet()
    opt = optim.SGD(lr=args.lr, momentum=0.9)
    if args.synthetic:
        images, labels = synthetic_mnist_arrays(train=True)
    else:
        from tpu_dist.data import MNIST
        ds = MNIST(root="./data", train=True)
        images = np.stack([np.asarray(x) for x, _ in ds])
        labels = np.array([y for _, y in ds])
    images = images.reshape(-1, 28, 28, 1).astype(np.float32) / 255.0
    labels = labels.astype(np.int32)

    def batch(step):
        # keyed on (rank, step) ONLY: a resumed run replays the same shard
        g = np.random.default_rng(10_000 * (rank + 1) + step)
        idx = g.integers(0, len(images), size=args.batch_size)
        return images[idx], labels[idx]

    @jax.jit
    def fwd_bwd(params, x, y):
        def loss(p):
            return F.cross_entropy(model.apply(p, x), y)
        return jax.value_and_grad(loss)(params)

    log = MetricLogger(every=25, fmt="[elastic] step {step} loss {loss:.4f}")
    params0 = model.init(jax.random.PRNGKey(0))

    from tpu_dist import checkpoint as ckpt
    stop = ckpt.GracefulShutdown().__enter__() if args.exit_on_preempt \
        else None   # entered for the process lifetime

    def preempted(ts, state, step):
        """SIGTERM arrived: save NOW (the cadence save may be steps away)
        and exit the elastic-shrink protocol code so the supervisor
        re-forms without this rank instead of burning restarts.  The exit
        must be `os._exit` — a normal sys.exit runs the jax coordination
        service's atexit teardown, which blocks on the still-running
        peers and deadlocks the gang; the checkpoint is already fsync'd
        and the supervisor only needs the exit code."""
        if stop is None or not stop.requested:
            return False
        ts.save(state, step)
        print(f"[elastic] rank preempted at step {step}; exiting "
              f"{resilience.PREEMPTED_EXIT_CODE} for an elastic shrink",
              flush=True)
        os._exit(resilience.PREEMPTED_EXIT_CODE)

    if args.zero:
        from tpu_dist.parallel import ZeroOptimizer
        zopt = ZeroOptimizer(opt, group=pg)
        with resilience.TrainState(args.ckpt_root,
                                   save_every=args.save_every, keep=3,
                                   shard=(rank, nproc),
                                   sharded_keys=("zero",)) as ts:
            state, start = ts.resume({"params": params0,
                                      "zero": zopt.init(params0)})
            params, zstate = state["params"], state["zero"]
            if start:
                rank_zero_print(f"[elastic] resumed at step {start} (ZeRO)")
            handle = None
            for step in range(start, args.max_steps):
                x, y = batch(step)          # staged under the in-flight …
                if handle is not None:
                    params = handle.wait(timeout=300)  # … param gather
                l, g = fwd_bwd(params, x, y)
                rs = zopt.reduce_scatter(jax.tree.map(np.asarray, g),
                                         group=pg)
                loss_now = float(l)         # overlaps the reduce-scatter
                handle, zstate = zopt.update(rs, zstate, group=pg)
                log.push(step=step, loss=loss_now)
                if args.save_every and step % args.save_every == 0:
                    params = handle.wait(timeout=300)  # checkpoint needs it
                ts.end_step({"params": params, "zero": zstate}, step)
                if stop is not None and stop.requested:
                    params = handle.wait(timeout=300)
                    preempted(ts, {"params": params, "zero": zstate}, step)
            params = handle.wait(timeout=300) if handle is not None \
                else params
        rank_zero_print(f"[elastic] done at step {args.max_steps}")
        return

    bucketer = C.Bucketer()  # bucketed async grad sync (25 MiB buckets)
    with resilience.TrainState(args.ckpt_root, save_every=args.save_every,
                               keep=3) as ts:
        state, start = ts.resume({"params": params0,
                                  "opt": opt.init(params0)})
        params, opt_state = state["params"], state["opt"]
        if start:
            rank_zero_print(f"[elastic] resumed at step {start}")
        for step in range(start, args.max_steps):
            x, y = batch(step)
            l, g = fwd_bwd(params, x, y)
            if nproc > 1:
                # issue the bucketed async all-reduce, then overlap the
                # loss readback (a device sync) with the wire transfer
                work = bucketer.all_reduce(jax.tree.map(np.asarray, g),
                                           op="avg", group=pg)
                loss_now = float(l)
                g = work.wait_all(timeout=300)
            else:
                loss_now = float(l)
            params, opt_state = opt.update(g, opt_state, params)
            log.push(step=step, loss=loss_now)
            ts.end_step({"params": params, "opt": opt_state}, step)
            preempted(ts, {"params": params, "opt": opt_state}, step)
    rank_zero_print(f"[elastic] done at step {args.max_steps}")
    dist.destroy_process_group()


if __name__ == "__main__":
    main()

"""Benchmark: MNIST ConvNet DDP training throughput (images/sec/chip).

The BASELINE.json metric model.  Runs the full fused train step (fwd + loss
+ grad allreduce + SGD update) through the DistributedDataParallel wrapper
over all available devices, and names the device it ran on in its output.
It refuses a device it has no peak for: a number from a CPU run is never
written under the name of a device metric.

Configuration: **bf16 mixed precision + scanned steps**.

- ``compute_dtype=bfloat16`` runs forward/backward on the MXU in bf16 while
  parameters, gradients, and optimizer state stay float32 master copies
  (numerics validated in tests/test_ddp_features.py).
- ``ddp.train_chunk`` executes BENCH_STEPS fused steps per host dispatch as
  a ``lax.scan`` (one XLA program, one readback), so host dispatch is paid
  once per BENCH_STEPS steps.
- Per-chip batch 8192; step inputs are generated ON DEVICE (jitted PRNG).

``BENCH_DTYPE=float32 BENCH_BATCH=2048`` is the f32 configuration; the
printed JSON carries ``dtype`` so the two are distinguishable.

Prints ONE JSON line: the median rate over BENCH_REPS timed chunks with
every repetition beside it, compile/warm-up seconds apart, and
``platform`` / ``device_kind`` / ``n_devices`` as JAX reports them.  This
is the one pre-ledger metric; the benchmark proper (cells, regression
bounds, trace reduction) is ROADMAP Speed item 1.
"""

import json
import os
import statistics
import sys
import time

# bf16 peak per chip in TFLOP/s, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).  A
# device that is not in the table is an error, not a default.
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0}

# fwd/image: conv1 2*26*26*32*25 + conv2 2*11*11*64*288 + conv3
# 2*8*8*128*576 + fc 2*2048*10 = 15,020,288; train ~= 3x fwd
TRAIN_FLOPS_PER_IMAGE = 3 * 15_020_288


def run() -> dict:
    """Measure and return the record."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import tpu_dist.dist as dist

    per_chip_batch = int(os.environ.get("BENCH_BATCH", 8192))
    steps = max(1, int(os.environ.get("BENCH_STEPS", 50)))
    reps = max(1, int(os.environ.get("BENCH_REPS", 8)))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    compute_dtype = None if dtype == "float32" else jnp.dtype(dtype)

    dev = jax.devices()[0]
    if dev.device_kind not in PEAK_BF16_TFLOPS:
        raise RuntimeError(
            f"bench.py has no peak for device_kind {dev.device_kind!r} "
            f"(platform {dev.platform!r}); known: "
            f"{sorted(PEAK_BF16_TFLOPS)}.  Add the device with its source, "
            f"or run on one that is listed")

    own_group = not dist.is_initialized()
    pg = dist.init_process_group() if own_group else dist.get_default_group()
    try:
        return _measure(pg, per_chip_batch, steps, reps, dtype,
                        compute_dtype)
    finally:
        if own_group:
            dist.destroy_process_group()


def _measure(pg, per_chip_batch, steps, reps, dtype, compute_dtype):
    import jax
    import jax.numpy as jnp
    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.models import ConvNet
    from tpu_dist.parallel import DistributedDataParallel
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_chips = dist.get_world_size()
    batch = per_chip_batch * n_chips
    dev = pg.devices[0]

    ddp = DistributedDataParallel(
        ConvNet(), optimizer=optim.SGD(lr=1e-4),
        loss_fn=nn.CrossEntropyLoss(), group=pg, donate=True,
        compute_dtype=compute_dtype)

    data_sharding = NamedSharding(pg.mesh, P(None, pg.axis_name))

    @jax.jit
    def make_data(key):
        kx, ky = jax.random.split(key)
        xs = jax.random.normal(kx, (steps, batch, 28, 28, 1), jnp.float32)
        ys = jax.random.randint(ky, (steps, batch), 0, 10, jnp.int32)
        return (jax.lax.with_sharding_constraint(xs, data_sharding),
                jax.lax.with_sharding_constraint(ys, data_sharding))

    xs, ys = jax.block_until_ready(make_data(jax.random.key(0)))

    def run_chunk():
        # fresh state per rep: donated buffers cannot be reused
        state = jax.block_until_ready(ddp.init(seed=0))
        t0 = time.perf_counter()
        state, m = ddp.train_chunk(state, xs, ys)
        jax.block_until_ready((state, m))
        return time.perf_counter() - t0

    setup_s = run_chunk()            # trace + compile + first run
    rates = [batch * steps / run_chunk() / n_chips for _ in range(reps)]
    value = statistics.median(rates)
    tflops = value * TRAIN_FLOPS_PER_IMAGE / 1e12
    return {
        "metric": "mnist_convnet_train_images_per_sec_per_chip",
        "value": round(value, 1),
        "unit": "images/sec/chip",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": n_chips,
        "dtype": dtype,
        "batch_per_chip": per_chip_batch,
        "steps_per_chunk": steps,
        "reps": [round(r, 1) for r in sorted(rates)],
        "setup_s": round(setup_s, 2),
        "achieved_model_tflops": round(tflops, 2),
        "model_flops_utilization": round(tflops / PEAK_BF16_TFLOPS[dev.device_kind], 4),
    }


def main():
    print(json.dumps(run()))


if __name__ == "__main__":
    main()

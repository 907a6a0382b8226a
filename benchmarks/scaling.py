"""Weak-scaling overhead estimate on a virtual 1..32-device CPU mesh.

Without pod hardware, true ICI scaling
efficiency (BASELINE.md north star: >=90% linear, 1->32 chips) cannot be
measured.  What CAN be measured in-repo is the *framework + collective
overhead* the compiled DDP step adds as the world grows: run the fused step
at world sizes 1,2,4,8,16,32 — the full north-star range — on
``--xla_force_host_platform_device_count=32`` CPU devices with constant
per-device batch.

The host may have only ONE physical core, so the N virtual devices' compute
serializes: ideal weak scaling here is ``t_N = N * t_1``, and we report

    serialized_efficiency(N) = (N * t_1) / t_N

which is 1.0 when the allreduce + shard_map machinery adds nothing beyond
the serialized compute, and drops below 1.0 by exactly the added overhead.
On real ICI the compute term is concurrent instead of serial, so this is an
upper bound on the per-step overhead, not a throughput prediction.

Always a CPU measurement: it runs itself in a subprocess with a forced
max(world_sizes)-device CPU backend, whatever the calling process holds.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


DEFAULT_WORLD_SIZES = (1, 2, 4, 8, 16, 32)  # BASELINE.md north star: 1->32


def _measure(per_device_batch: int = 32, steps: int = 6,
             reps: int = 3, world_sizes=DEFAULT_WORLD_SIZES) -> dict:
    """Run inside a process whose backend has >= max(world_sizes) devices."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.models import ConvNet
    from tpu_dist.parallel import DistributedDataParallel
    from benchmarks.timing import ddp_repeat_step_time

    dist.init_process_group(backend="cpu")
    rng = np.random.default_rng(0)
    times = {}
    for n in world_sizes:
        pg = dist.new_group(ranks=range(n))
        ddp = DistributedDataParallel(
            ConvNet(), optimizer=optim.SGD(lr=1e-4),
            loss_fn=nn.CrossEntropyLoss(), group=pg, donate=True)
        sharding = NamedSharding(pg.mesh, P(pg.axis_name))
        batch = per_device_batch * n
        x = jax.device_put(
            rng.normal(size=(batch, 28, 28, 1)).astype(np.float32), sharding)
        y = jax.device_put(rng.integers(0, 10, batch).astype(np.int32),
                           sharding)

        times[n] = ddp_repeat_step_time(ddp, x, y, steps=steps, reps=reps)
    dist.destroy_process_group()

    t1 = times[1]
    return {
        "metric": "ddp_weak_scaling_overhead_virtual_cpu_mesh",
        "step_ms": {str(n): round(t * 1e3, 3) for n, t in times.items()},
        "serialized_efficiency": {
            str(n): round(n * t1 / times[n], 3) for n in times},
        "per_device_batch": per_device_batch,
        "note": "1-core host: ideal t_N = N*t_1; see module docstring. "
                "Overhead RATIOS depend on the per-device work size, so "
                "the whole 1..32 ladder is recorded at ONE fixed "
                "per-device batch (r5 verdict #8: the r1-r3 rows used 128 "
                "over worlds 1..8 and an interim row used 8 over 1..32; "
                "this single consistent series replaces both).",
    }


def run(per_device_batch: int = 32, steps: int = 6, reps: int = 3,
        world_sizes=DEFAULT_WORLD_SIZES) -> dict:
    # batch 32 per device: one consistent production-like size across the
    # whole 1..32 ladder (r5 verdict #8), still small enough that the
    # 32x-serialized rung finishes inside the child timeout
    """Re-exec on a forced max(world_sizes)-device CPU backend and return
    the measurement."""
    code = (
        "import os, re\n"
        f"_flag = '--xla_force_host_platform_device_count="
        f"{max(world_sizes)}'\n"
        # drop any inherited device-count flag (e.g. conftest's =8) so the
        # requested count is the only one XLA sees
        "_rest = re.sub(r'--xla_force_host_platform_device_count=\\d+', '',\n"
        "               os.environ.get('XLA_FLAGS', ''))\n"
        "os.environ['XLA_FLAGS'] = (_rest + ' ' + _flag).strip()\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"import sys; sys.path.insert(0, {_REPO!r})\n"
        "import json\n"
        "from benchmarks.scaling import _measure\n"
        f"print('BENCH_JSON ' + json.dumps(_measure({per_device_batch}, "
        f"{steps}, {reps}, {tuple(world_sizes)!r})))\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], cwd=_REPO, env=env,
                          capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"scaling child failed (rc={proc.returncode}):\n"
                           f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_JSON "):
            return json.loads(line[len("BENCH_JSON "):])
    raise RuntimeError(f"no BENCH_JSON line in child output:\n"
                       f"{proc.stdout[-2000:]}")


if __name__ == "__main__":
    sys.path.insert(0, _REPO)
    print(json.dumps(run()))

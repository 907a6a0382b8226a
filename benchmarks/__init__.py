"""Extended benchmark harness — the BASELINE.md config ladder beyond the
headline metric that ``bench.py`` (repo root) prints.

- ``benchmarks.resnet_cifar``  — ladder #4: ResNet-18 CIFAR-10 bf16 DDP
  images/sec/chip on the real chip.
- ``benchmarks.scaling``       — weak-scaling overhead estimate on a virtual
  1..8-device CPU mesh (ladder #2/#3 stand-in without pod hardware).
- ``benchmarks.run_all``       — run everything, write BENCH_EXTENDED.json.

Shared timing discipline (benchmarks/timing.py): chained on-device steps
ended by a host readback, best-of-k (long - short) marginal step time.
"""

from .timing import chained_step_time, ddp_repeat_step_time  # noqa: F401

"""Profiling — jax.profiler hooks + step timing (SURVEY.md §5 tracing row;
the reference only has rank-0 wall-clock prints,
/root/reference/mpspawn_dist.py:94,120)."""

from __future__ import annotations

import contextlib
import time
from typing import List, Optional

__all__ = ["trace", "StepTimer"]


@contextlib.contextmanager
def trace(logdir: str, host_only_on_rank0: bool = True):
    """Capture a ``jax.profiler`` trace viewable in XProf/TensorBoard.

    The ``NCCL_DEBUG=INFO`` analogue for "what is the hardware doing":
    collectives show up as ops on the ICI DMA rows of the trace.
    """
    import jax
    from .. import dist as _dist

    skip = (host_only_on_rank0 and _dist.is_initialized()
            and _dist.get_rank() != 0)
    if skip:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock step timing with warmup exclusion and percentile summary.

    NOTE on async dispatch: a step's wall time only reflects device time if
    the loop blocks on the step's output (e.g. reads the loss or calls
    ``block_until_ready``); for throughput, time a scanned chunk of steps
    (``ddp.train_chunk``) so host dispatch is paid once per chunk.
    """

    def __init__(self, warmup: int = 3):
        self.warmup = warmup
        self._times: List[float] = []
        self._seen = 0
        self._t0: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._seen += 1
        if self._seen > self.warmup:
            self._times.append(dt)

    @property
    def steps(self) -> int:
        return len(self._times)

    def mean(self) -> float:
        return sum(self._times) / len(self._times) if self._times else 0.0

    def percentile(self, q: float) -> float:
        if not self._times:
            return 0.0
        s = sorted(self._times)
        idx = min(len(s) - 1, int(round(q / 100 * (len(s) - 1))))
        return s[idx]

    def summary(self) -> str:
        return (f"steps={self.steps} mean={self.mean()*1e3:.2f}ms "
                f"p50={self.percentile(50)*1e3:.2f}ms "
                f"p95={self.percentile(95)*1e3:.2f}ms")

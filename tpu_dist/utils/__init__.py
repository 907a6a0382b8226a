"""tpu_dist.utils — observability helpers (SURVEY.md §5: the reference's
tracing/metrics rows are bare prints; these are the structured equivalents)."""

from .backoff import BackoffDeadlineError, retry_call
from .compile_cache import ensure_compile_cache
from .logging import MetricLogger, log_event, rank_zero_print
from .memory import max_memory_allocated, memory_stats
from .metrics import (LatencyHistogram, collective_counters,
                      reset_collective_counters)
from .profiler import trace

__all__ = ["rank_zero_print", "MetricLogger", "log_event",
           "trace", "ensure_compile_cache",
           "retry_call", "BackoffDeadlineError",
           "collective_counters", "reset_collective_counters",
           "LatencyHistogram", "memory_stats", "max_memory_allocated"]

"""Streaming latency percentiles, and the per-collective transport
counters.

``LatencyHistogram`` is the one percentile engine of the serving layer and
of the host phases (:mod:`tpu_dist.obs.spans`).

The collective counters aggregate bytes/latency per (op, transport) for the
eager host collectives, so a training job can answer "how much gradient
traffic rode the p2p data plane vs. the store, and at what rate?" without a
profiler.  They live in :mod:`tpu_dist.obs.recorder` — the collectives
record into ONE ingestion point (``record_transport``) that feeds both the
aggregates and the armed event stream, so the counters and the flight
recorder can never disagree.  The two functions below are the operator's
read side (docs/collectives.md).
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Optional

__all__ = ["collective_counters", "reset_collective_counters",
           "LatencyHistogram"]


class LatencyHistogram:
    """Streaming latency percentiles without storing samples.

    Geometric buckets: bucket 0 is the underflow bucket (values below
    ``min_value``); bucket ``i >= 1`` covers
    ``[min_value*(1+resolution)^(i-1), min_value*(1+resolution)^i)`` and
    reports its upper edge, so any reported percentile is within a
    ``resolution`` relative error of the true sample — at a few KB of
    counts however many million observations arrive.  The final bucket is
    the unbounded overflow bucket and reports the observed max.  This is the shared
    percentile engine for the serving layer (per-request queue/TTFT/token
    latencies, :mod:`tpu_dist.serve`) and the benchmarks
    (``benchmarks/bench_serve.py``), which used to hand-roll ``sorted()``
    percentile math per bench.  Thread-safe; ``merge`` combines histograms
    from concurrent recorders.
    """

    def __init__(self, min_value: float = 1e-6, max_value: float = 3600.0,
                 resolution: float = 0.02):
        if not 0 < min_value < max_value:
            raise ValueError(f"need 0 < min_value < max_value, got "
                             f"{min_value}/{max_value}")
        if not 0 < resolution < 1:
            raise ValueError(f"resolution must be in (0, 1), got "
                             f"{resolution}")
        self.min_value = float(min_value)
        self.max_value = float(max_value)
        self.resolution = float(resolution)
        self._log1p = math.log1p(resolution)
        self._nbuckets = self._index(max_value) + 2  # + under/overflow slot
        self._counts = [0] * self._nbuckets
        self._mu = threading.Lock()
        self._n = 0
        self._sum = 0.0
        self._max = 0.0

    def _index(self, value: float) -> int:
        if value < self.min_value:
            return 0
        return 1 + int(math.log(value / self.min_value) / self._log1p)

    def observe(self, seconds: float) -> None:
        """Record one latency sample (negative values clamp to 0)."""
        v = float(seconds)
        # every span on the compiled path ends here: _index() inline
        if v >= self.min_value:
            i = 1 + int(math.log(v / self.min_value) / self._log1p)
            if i >= self._nbuckets:
                i = self._nbuckets - 1
        else:
            i = 0
            if not v > 0.0:
                v = 0.0
        with self._mu:
            self._counts[i] += 1
            self._n += 1
            self._sum += v
            if v > self._max:
                self._max = v

    def merge(self, other: "LatencyHistogram") -> None:
        """Fold ``other``'s counts into this histogram (must share the
        bucket geometry)."""
        if (other.min_value, other.max_value, other.resolution) != \
                (self.min_value, self.max_value, self.resolution):
            raise ValueError("histograms have different bucket geometry")
        with other._mu:
            counts = list(other._counts)
            n, s, mx = other._n, other._sum, other._max
        with self._mu:
            for i, c in enumerate(counts):
                self._counts[i] += c
            self._n += n
            self._sum += s
            self._max = max(self._max, mx)

    @property
    def count(self) -> int:
        return self._n

    def percentile(self, p: float) -> Optional[float]:
        """The ``p``-th percentile (0 < p <= 100), or None when empty.
        Returns the upper edge of the bucket holding the rank-``ceil(p/100
        * n)`` sample — within ``resolution`` relative error, clamped to
        the observed max."""
        if not 0 < p <= 100:
            raise ValueError(f"percentile must be in (0, 100], got {p}")
        with self._mu:
            if self._n == 0:
                return None
            rank = max(1, math.ceil(p / 100.0 * self._n))
            seen = 0
            for i, c in enumerate(self._counts):
                seen += c
                if seen >= rank:
                    if i >= self._nbuckets - 1:
                        # overflow bucket is unbounded above: the observed
                        # max is the only honest answer
                        return self._max
                    upper = (self.min_value * (1 + self.resolution) ** i
                             if i else self.min_value)
                    return min(upper, self._max)
            return self._max

    def summary(self) -> Dict[str, float]:
        """``{count, mean, max, p50, p95, p99}`` (zeros when empty)."""
        with self._mu:
            n, s, mx = self._n, self._sum, self._max
        if n == 0:
            return {"count": 0, "mean": 0.0, "max": 0.0,
                    "p50": 0.0, "p95": 0.0, "p99": 0.0}
        return {"count": n, "mean": s / n, "max": mx,
                "p50": self.percentile(50), "p95": self.percentile(95),
                "p99": self.percentile(99)}


# -- host-collective transport counters (shims over tpu_dist.obs) -------------


def collective_counters(reset: bool = False) -> Dict[str, Dict[str, float]]:
    """Snapshot of the per-``op/transport`` counters, each entry
    ``{calls, bytes, seconds, mb_per_s}``.  ``reset=True`` atomically
    clears after reading (per-step deltas).  Reads the obs event-stream
    aggregates (:func:`tpu_dist.obs.recorder.transport_counters`)."""
    from ..obs import recorder as _obs
    return _obs.transport_counters(reset=reset)


def reset_collective_counters() -> None:
    from ..obs import recorder as _obs
    _obs.reset_transport_counters()

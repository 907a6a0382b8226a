"""utils: metric logger windows + step timer + streaming latency
histogram (the serve/bench percentile engine)."""

import threading

import numpy as np
import pytest

from tpu_dist.utils import LatencyHistogram, MetricLogger


class TestLatencyHistogram:
    def test_percentiles_within_resolution(self):
        # the whole point: p50/p95/p99 without storing samples, within the
        # bucket geometry's relative error of numpy's exact answer
        rng = np.random.default_rng(0)
        samples = rng.lognormal(mean=-3.0, sigma=1.0, size=20_000)
        h = LatencyHistogram(resolution=0.02)
        for s in samples:
            h.observe(s)
        assert h.count == len(samples)
        for p in (50, 95, 99):
            exact = float(np.percentile(samples, p))
            got = h.percentile(p)
            # bucket upper edge: within ~2x resolution relative error
            assert abs(got - exact) / exact < 0.05, (p, got, exact)
        s = h.summary()
        assert s["count"] == len(samples)
        assert abs(s["mean"] - samples.mean()) / samples.mean() < 1e-6
        assert s["max"] == samples.max()

    def test_empty_and_validation(self):
        h = LatencyHistogram()
        assert h.percentile(99) is None
        assert h.summary()["count"] == 0
        with pytest.raises(ValueError):
            h.percentile(0)
        with pytest.raises(ValueError):
            LatencyHistogram(min_value=1.0, max_value=0.5)
        with pytest.raises(ValueError):
            LatencyHistogram(resolution=0)

    def test_clamps_and_extremes(self):
        h = LatencyHistogram(min_value=1e-6, max_value=10.0)
        h.observe(-5.0)          # clamps to 0 -> underflow bucket
        h.observe(1e9)           # overflow bucket
        assert h.count == 2
        assert h.percentile(100) == 1e9   # clamped to the observed max

    def test_merge(self):
        a, b = LatencyHistogram(), LatencyHistogram()
        for v in (0.01, 0.02, 0.03):
            a.observe(v)
        for v in (0.04, 0.05):
            b.observe(v)
        a.merge(b)
        assert a.count == 5
        assert a.summary()["max"] == 0.05
        with pytest.raises(ValueError):
            a.merge(LatencyHistogram(resolution=0.1))

    def test_thread_safety_counts(self):
        h = LatencyHistogram()

        def work():
            for _ in range(2000):
                h.observe(0.001)

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert h.count == 8000


class TestMetricLogger:
    def test_window_average(self, capsys):
        log = MetricLogger(every=3, fmt="s{step} loss={loss:.2f}")
        out = None
        for i in range(6):
            out = log.push(step=i + 1, loss=float(i))
        # windows: [0,1,2] -> 1.0 at step 3; [3,4,5] -> 4.0 at step 6
        assert out == {"loss": 4.0}
        printed = capsys.readouterr().out
        assert "s3 loss=1.00" in printed and "s6 loss=4.00" in printed

    def test_ratio_pairs(self):
        log = MetricLogger(every=2)
        log.push(step=1, acc=(3, 10))
        out = log.push(step=2, acc=(7, 10))
        assert out == {"acc": 0.5}

    def test_fractional_denominator(self):
        log = MetricLogger(every=1)
        assert log.push(step=1, frac=(0.3, 0.5)) == {"frac": 0.6}
        assert log.push(step=2, z=(1.0, 0.0)) == {"z": 0.0}  # empty window

    def test_incomplete_window_returns_none(self):
        log = MetricLogger(every=5)
        assert log.push(step=1, loss=1.0) is None

    def test_device_scalars(self):
        import jax.numpy as jnp
        log = MetricLogger(every=2)
        log.push(step=1, loss=jnp.asarray(2.0))
        out = log.push(step=2, loss=jnp.asarray(4.0))
        assert out == {"loss": 3.0}

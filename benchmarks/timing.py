"""Chained-step marginal timing.

Chain ``k`` steps through their state dependency, read back one scalar
(the sync), and take the (long - short) chain difference so the constant
dispatch/readback overhead cancels.  Donation-safe: a fresh state is built
per chain.  A min-of-differences estimator cannot show spread or a
regression; ROADMAP Speed item 1 replaces it with repeated timed windows.
"""

from __future__ import annotations

import time
from typing import Callable


def _sync(metrics) -> None:
    float(metrics)


def chained_step_time(step: Callable, make_state: Callable[[], object],
                      *, steps: int = 100, reps: int = 3,
                      warmup: int = 5) -> float:
    """Marginal seconds/step of ``state, scalar = step(state)``.

    ``step`` must return ``(new_state, scalar_metric)`` with the scalar
    depending on the whole chain (e.g. the loss); ``make_state`` builds a
    fresh initial state (donated buffers cannot be reused across chains).
    """

    def chain(k: int) -> float:
        state = make_state()
        t0 = time.perf_counter()
        m = None
        for _ in range(k):
            state, m = step(state)
        _sync(m)
        return time.perf_counter() - t0

    chain(warmup)  # compile + warm
    n_short = max(5, steps // 10)
    d_short = min(chain(n_short) for _ in range(reps))
    d_long = min(chain(steps + n_short) for _ in range(reps))
    return (d_long - d_short) / steps


def ddp_repeat_step_time(ddp, x, y, *, steps: int = 50, reps: int = 6,
                         warmup: int = 1, min_window: float = 0.5,
                         max_steps: int = 4096) -> float:
    """Marginal seconds/step of a DDP train step, scan-timed.

    Supersedes :func:`chained_step_time` for DDP workloads:
    ``ddp.train_repeat`` runs k steps per dispatch as one XLA program, so
    per-step host dispatch is out of the window; min-over-reps takes the
    quietest window, and a long-minus-short difference cancels the
    remaining constant dispatch+readback overhead.

    The chunk is auto-sized so the differenced compute window is at least
    ``min_window`` seconds — for fast steps a small fixed chunk would leave
    (long - short) comparable to contention noise in the minima (observed:
    negative differences on 2 ms steps with a 20-step chunk).  Each resize
    costs one extra compile; capped at ``max_steps``.
    """

    def run_k(k: int) -> float:
        state = ddp.init(seed=0)  # fresh: donated buffers can't be reused
        t0 = time.perf_counter()
        state, m = ddp.train_repeat(state, x, y, k)
        _sync(m["loss"][-1])
        return time.perf_counter() - t0

    n_short = max(1, min(steps - 1, steps // 5))
    for _ in range(max(1, warmup)):  # compile both shapes + warm
        run_k(steps)
        run_k(n_short)
    t_est = run_k(steps) / steps
    if (steps - n_short) * t_est < min_window:
        steps = min(max_steps,
                    n_short + int(min_window / max(t_est, 1e-7)) + 1)
        run_k(steps)  # compile the resized chunk
    d_long = min(run_k(steps) for _ in range(reps))
    d_short = min(run_k(n_short) for _ in range(reps))
    diff = (d_long - d_short) / (steps - n_short)
    # under extreme contention the minima can still cross; the long chunk's
    # gross time/step is then a safe (over-)estimate, never a negative one
    return diff if diff > 0 else d_long / steps

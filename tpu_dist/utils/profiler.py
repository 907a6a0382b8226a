"""Profiling — the jax.profiler hook (SURVEY.md §5 tracing row; the
reference only has rank-0 wall-clock prints,
/root/reference/mpspawn_dist.py:94,120)."""

from __future__ import annotations

import contextlib

__all__ = ["trace"]


@contextlib.contextmanager
def trace(logdir: str, host_only_on_rank0: bool = True):
    """Capture a ``jax.profiler`` trace viewable in XProf/TensorBoard.

    The ``NCCL_DEBUG=INFO`` analogue for "what is the hardware doing":
    collectives show up as ops on the ICI DMA rows of the trace, every
    device operation carries its module path (``jax.named_scope``), and the
    host phases of :mod:`tpu_dist.obs.spans` (``td/decode.dispatch``, ...)
    lie beside them on the same clock.  The Python function tracer is off:
    it slows the host it measures and adds nothing the spans do not name.
    """
    import jax
    from .. import dist as _dist

    skip = (host_only_on_rank0 and _dist.is_initialized()
            and _dist.get_rank() != 0)
    if skip:
        yield
        return
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()

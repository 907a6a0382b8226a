"""Device-memory introspection — ``torch.cuda.max_memory_allocated`` for TPU
HBM.

The reference stack debugs OOMs with ``torch.cuda.max_memory_allocated()``;
the TPU equivalent is the per-device allocator statistics XLA publishes
through ``jax.Device.memory_stats()``, in bytes, defaulting to
``jax.devices()[0]``.

A TPU publishes real allocator statistics (``chip_smoke.py`` asserts a
non-zero peak on every device).  The CPU host-platform backend used by the
virtual-mesh tests publishes none and reads 0 rather than raising, so
instrumented training loops run unchanged there.  There is no
``reset_peak_memory_stats`` parity: the XLA allocator's peak counter is
cumulative per process and cannot be reset from JAX.
"""

from __future__ import annotations

from typing import Dict

__all__ = ["memory_stats", "max_memory_allocated"]


def _device(device=None):
    import jax
    return jax.devices()[0] if device is None else device


def memory_stats(device=None) -> Dict[str, int]:
    """Raw allocator statistics for ``device`` (default: first device).

    Keys follow XLA's naming: ``bytes_in_use``, ``peak_bytes_in_use``,
    ``bytes_limit``, ``largest_alloc_size``, ... — empty dict when the
    platform publishes none (CPU).  torch analogue:
    ``torch.cuda.memory_stats``.
    """
    stats = _device(device).memory_stats()
    return dict(stats) if stats else {}


def max_memory_allocated(device=None) -> int:
    """High-water mark of the bytes held by live buffers on ``device`` over
    the process lifetime.  torch analogue:
    ``torch.cuda.max_memory_allocated``."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))

"""Device-memory introspection — torch.cuda.memory_* parity for TPU HBM.

The reference stack debugs OOMs with ``torch.cuda.memory_allocated()`` /
``max_memory_allocated()`` / ``mem_get_info()``; the TPU equivalent is the
per-device allocator statistics XLA publishes through
``jax.Device.memory_stats()``.  This module wraps them under the familiar
names, in bytes, defaulting to ``jax.devices()[0]``.

A TPU publishes real allocator statistics (``chip_smoke.py`` asserts a
non-zero peak on every device).  The CPU host-platform backend used by the
virtual-mesh tests publishes none and reads 0 / ``(0, 0)`` rather than
raising, so instrumented training loops run unchanged there.  There is no
``reset_peak_memory_stats`` parity: the XLA allocator's peak counter is
cumulative per process and cannot be reset from JAX.
"""

from __future__ import annotations

from typing import Dict, Tuple

__all__ = ["memory_stats", "memory_allocated", "max_memory_allocated",
           "mem_get_info", "memory_summary"]


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


def memory_allocated(device=None) -> int:
    """Bytes currently held by live buffers on ``device`` (0 when the
    platform publishes no stats).  torch analogue:
    ``torch.cuda.memory_allocated``."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """High-water mark of ``memory_allocated`` over the process lifetime.
    torch analogue: ``torch.cuda.max_memory_allocated``."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def mem_get_info(device=None) -> Tuple[int, int]:
    """``(free_bytes, total_bytes)`` for ``device`` — torch analogue:
    ``torch.cuda.mem_get_info``.  ``(0, 0)`` when stats are unavailable."""
    stats = memory_stats(device)
    total = int(stats.get("bytes_limit", 0))
    return max(0, total - int(stats.get("bytes_in_use", 0))), total


def memory_summary(device=None) -> str:
    """Human-readable snapshot (torch.cuda.memory_summary analogue)."""
    d = _device(device)
    stats = memory_stats(d)
    if not stats:
        return f"{d}: no allocator statistics published on this platform"
    gib = 1 << 30
    lines = [f"{d} memory summary:"]
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size"):
        if key in stats:
            lines.append(f"  {key:<22} {stats[key] / gib:8.3f} GiB")
    extra = sorted(k for k in stats
                   if k not in ("bytes_in_use", "peak_bytes_in_use",
                                "bytes_limit", "largest_alloc_size"))
    for key in extra:
        lines.append(f"  {key:<22} {stats[key]}")
    return "\n".join(lines)

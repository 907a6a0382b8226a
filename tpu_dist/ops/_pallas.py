"""Shared plumbing for the Pallas kernels in this package."""

from __future__ import annotations

import jax

__all__ = ["use_interpret", "out_struct", "ceil_to", "sublane_tile"]


def ceil_to(x: int, m: int) -> int:
    """Round ``x`` up to a multiple of ``m`` (tile/lane alignment)."""
    return (x + m - 1) // m * m


def sublane_tile(dtype) -> int:
    """Rows in Mosaic's minimum (sublane x 128-lane) tile for ``dtype``:
    8 for 4-byte types, 16 for bf16/f16, 32 for 1-byte types — sub-32-bit
    rows pack along the sublanes, so a row block below this is off the
    tiling."""
    return 8 * max(1, 4 // jax.numpy.dtype(dtype).itemsize)


def use_interpret() -> bool:
    """Compiled Mosaic on TPU; the HLO interpreter everywhere else.

    NOTE every kernel body in this package is wrapped in ``pl.when`` (a
    causal tile-skip predicate, or a trivially-true one).  That is not only
    an optimization: the HLO interpreter's discharge of a *bare* kernel
    body trips shard_map's varying-manual-axes check (ops mixing
    device-varying block data with invariant constants), while the
    ``pl.when``-discharged form composes — and the DDP wrapper and
    ring-attention flash path trace these kernels inside shard_map.
    """
    return jax.default_backend() != "tpu"


def out_struct(shape, dtype, *operands):
    """ShapeDtypeStruct carrying the union of the operands' varying-mesh-
    axes sets — required for pallas_call outputs traced inside shard_map
    (e.g. under the DDP wrapper), harmless outside it."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

"""What the scalar-decay chunked scan needs, and the kernel's share of its
roofline from a reduced trace.

``tpu_dist/ops/delta_scan.py`` names its ``pallas_call`` ``delta_scan``: one
call a Gated DeltaNet layer a prefill, every value head's float32 ``(Dk,
Dv)`` state advanced over the bucket's positions.  What a call needs is the
MATHEMATICS', whatever the kernel does: ``q``, ``k`` (``Dk`` a KEY head a
position), ``v`` (``Dv`` a value head), ``g`` and ``beta`` (a number a value
head) read and ``o`` (``Dv`` a value head) written ONCE, float32, and each
value head's state read and written once; the operations of the WY form a
value head a chunk of ``C = 64`` positions, the unit-triangular system
solved by substitution (``C^2`` operations a column, not the doubling's
products): ``k k^T`` and ``q k^T`` (``2 C^2 Dk`` each), the solve applied to
``[v beta | k beta e^g]`` (``C^2 (Dv + Dk)``), ``k_cum S``, ``q S`` and
``k^T v_new`` (``2 C Dk Dv`` each) and ``within v_new`` (``2 C^2 Dv``),
counted once against ``bf16_flops_per_s`` as ``flops.roofline`` counts every
operation (the kernel spends three bfloat16 passes a product).  The copies
the wrapper makes of the small operands are nobody's need.  By this count a
call is memory bound (0.25 ms a layer of the hybrid cell against 0.11 ms of
operations), so the share cannot pass 100% unless the kernel moves less than
its operands.

A call's positions are read off its traced row, whose label ends with the
result's types: the output's, ``f32[rows, positions, Hv x Dv]``, first.
"""

from __future__ import annotations

import re

from . import flops, trace_reduce

KERNEL = "delta_scan"
CHUNK = 64
_OUTPUT = re.compile(r"f32\[(\d+),(\d+),\d+\]")


def call(positions: float, key_heads: int, value_heads: int, k_dim: int,
         v_dim: int) -> dict:
    """One call over ``positions`` (rows times the bucket) and one row's
    states, as operations and HBM bytes."""
    c = CHUNK
    chunk_head = (4 * c * c * k_dim + c * c * (v_dim + k_dim)
                  + 2 * c * c * v_dim + 6 * c * k_dim * v_dim)
    numbers = (positions * (2 * key_heads * k_dim + 2 * value_heads * v_dim
                            + 2 * value_heads)
               + 2 * value_heads * k_dim * v_dim)
    return {"flops": float(chunk_head) * value_heads * positions / c,
            "bytes": 4.0 * numbers}


def roofline_share(reduced: dict, key_heads: int, value_heads: int,
                   k_dim: int, v_dim: int, peak: dict):
    """Least seconds the chip could take for the traced calls over the
    seconds they took, in percent; None where the trace holds no such call
    or a call's row does not say its positions."""
    least = taken = 0.0
    for name, start, end in reduced.get("rows0", ()):
        if KERNEL not in name.split(" ", 1)[0]:
            continue
        shape = _OUTPUT.search(name)
        if shape is None:
            return None
        rows, bucket = map(int, shape.groups())
        # the states of every row of the call
        one = call(bucket, key_heads, value_heads, k_dim, v_dim)
        least += rows * flops.roofline(one["flops"], one["bytes"], peak)[0]
        taken += (end - start) * 1e-9
    return 100.0 * least / taken if taken else None

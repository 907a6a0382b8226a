"""The gated delta rule's one-token update — a Pallas TPU kernel that reads
each slot's state once and writes it once, in place.

One decode step of a recurrent layer (tpu_dist/nn/deltanet.py:
:class:`~tpu_dist.nn.GatedDeltaNet`, :class:`~tpu_dist.nn.KimiDeltaAttention`)
updates every slot's ``(Dk, Dv)`` float32 state a head by ONE token::

    sk = S^T (decay * k);  sq = S^T (decay * q)
    delta = beta * (v - sk);  o = sq + (k . q) * delta
    S' = decay[:, None] * S + k[:, None] * delta[None, :]

(``gated_delta_step``'s formulas; ``decay * S`` is formed once and both
contractions read it).  The rank-one update of a tile needs ``S^T k`` of that
whole tile first, a dependency no fusion of two HLO operations expresses: as
``jax.numpy`` the state goes out to HBM between the contraction and the
update, three passes over it in four passes' worth of time (PERF.md, PRs 30,
40).  Here a grid step holds ``HEADS_BLOCK`` heads' tiles of one slot in VMEM:

- the state is read HBM -> VMEM once, and the new state written once OVER it
  (``input_output_aliases``: donate the state; a decode program's pool leaf
  is updated in place);
- everything is float32 on the VPU: a contraction over ``Dk`` multiplies each
  row of the tile by its entry of the column, adds the tile's row-vregs and
  reduces the 8 sublanes once.  Nothing passes the MXU, whose bfloat16 passes
  a float32 contraction would have to be split into;
- the vectors that scale the state's ROWS (``decay``, ``k``, ``q``) arrive
  transposed, ``Dk`` in the sublanes as the state has it and ``[decay | k |
  q]`` of the block's heads side by side in the lanes: a ``(Dk, 3 x heads)``
  array a block (one lane tile for 32 heads), a head's column taken by a lane
  slice and a lane broadcast.  Shaped ``(.., Dk, 1)`` each would pad its one
  lane to 128 and weigh what the state does.  ``v``, ``sk``, ``sq``,
  ``delta``, ``o`` are rows along ``Dv`` and need nothing.

The decay's rank comes from ``g``'s shape as ``gated_delta_step`` takes it: a
number a head is spread along ``Dk`` and the same kernel runs.  A row with
``g = 0`` and ``beta = 0`` leaves its state bit for bit.  No backward: the
differentiable forward keeps the ``jax.numpy`` form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._pallas import (out_struct as _out_struct, sublane_tile,
                      use_interpret as _use_interpret)

__all__ = ["delta_step", "delta_step_ok"]

_LANE = 128
# Heads of one slot a grid step holds: 1 MiB of state in and 1 MiB out, double
# buffered.  Timed on the chip at 120 and 96 slots of 32 heads of 128 x 128
# (PERF.md, PR 41): 8, 16 and 32 heads a block within 1% of one another
# (0.84-0.85 ms a call by the host's clock; HBM sets the pace).
HEADS_BLOCK = 16


def delta_step_ok(state) -> bool:
    """Whether the kernel takes this state leaf: float32 ``(B, H, Dk, Dv)``
    whose ``Dv`` fills whole lanes and whose ``Dk`` whole sublane tiles."""
    return (state.ndim == 4 and state.dtype == jnp.float32
            and state.shape[-1] % _LANE == 0
            and state.shape[-2] % sublane_tile(state.dtype) == 0)


def _heads_block(heads: int) -> int:
    """Heads of one slot a grid step holds: ``HEADS_BLOCK`` or the most
    below it that divide ``heads`` in whole sublane tiles (a block of ``v``
    rows is ``(heads a block, Dv)``), else all of them."""
    fit = [n for n in range(8, HEADS_BLOCK + 1, 8) if heads % n == 0]
    return max(fit) if heads > HEADS_BLOCK and fit else heads


def _kernel(s_ref, cols_ref, v_ref, scal_ref, o_ref, so_ref, *, hb):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) >= 0)
    def _():
        tile = s_ref.shape[2:]
        cols = cols_ref[0, 0]                                # (Dk, 3 hb)
        scal = scal_ref[0, 0]                                # (2, hb)
        column = lambda j: jnp.broadcast_to(cols[:, j:j + 1], tile)
        for h in range(hb):
            decay, k, q = column(h), column(hb + h), column(2 * hb + h)
            beta, kq = scal[0:1, h:h + 1], scal[1:2, h:h + 1]
            s = s_ref[0, h] * decay
            sk = jnp.sum(s * k, axis=0, keepdims=True)       # (1, Dv)
            sq = jnp.sum(s * q, axis=0, keepdims=True)
            delta = beta * (v_ref[0, h:h + 1, :] - sk)
            o_ref[0, h:h + 1, :] = sq + kq * delta
            so_ref[0, h] = s + k * delta


def delta_step(state, q, k, v, g, beta):
    """One token a row: :func:`tpu_dist.nn.deltanet.gated_delta_step`'s
    signature, shapes and float32 types.  ``state`` ``(B, H, Dk, Dv)``;
    ``q``, ``k`` ``(B, H, Dk)`` normalised, ``v`` ``(B, H, Dv)``; ``g`` (log
    decay, <= 0) ``(B, H)``, or ``(B, H, Dk)`` for a decay a channel;
    ``beta`` ``(B, H)``.  Returns ``(o (B, H, Dv), new state)``; the state
    is aliased to the result: donate it."""
    return _call(state, q, k, v, g, beta, interpret=_use_interpret())


# jitted so that a model's layers share ONE trace and ONE Mosaic lowering
@functools.partial(jax.jit, static_argnames="interpret")
def _call(state, q, k, v, g, beta, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, dk, dv = state.shape
    hb = _heads_block(heads)
    blocks = heads // hb
    decay = jnp.exp(g)
    if g.ndim < k.ndim:
        decay = jnp.broadcast_to(decay[..., None], k.shape)
    # (B, blocks, Dk, 3 hb): Dk in the sublanes as the state has it, the
    # block's heads of [decay | k | q] in the lanes
    cols = jnp.stack([decay, k, q], axis=1).reshape(b, 3, blocks, hb, dk)
    cols = cols.transpose(0, 2, 4, 1, 3).reshape(b, blocks, dk, 3 * hb)
    scal = jnp.stack([beta, jnp.sum(k * q, -1)], axis=1)     # (B, 2, H)
    scal = scal.reshape(b, 2, blocks, hb).swapaxes(1, 2)
    rows = pl.BlockSpec((1, hb, dv), lambda i, j: (i, j, 0))
    tiles = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0))
    operands = (state, cols, v, scal)
    out, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid=(b, blocks),
        in_specs=[tiles,
                  pl.BlockSpec((1, 1, dk, 3 * hb), lambda i, j: (i, j, 0, 0)),
                  rows,
                  pl.BlockSpec((1, 1, 2, hb), lambda i, j: (i, j, 0, 0))],
        out_specs=[rows, tiles],
        out_shape=[_out_struct(v.shape, v.dtype, *operands),
                   _out_struct(state.shape, state.dtype, *operands)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="delta_step",
    )(*operands)
    return out, state

"""The combine of an expert layer that holds a share of its experts — the
held picks' rows walked in token order and added up per token by a Pallas
TPU kernel.

``y[t] = sum_j w[j, t] * out[slot[j, t]]`` over a token's ``k`` picks, a pick
of an absent expert (``slot == len(out)``) adding nothing.  As a row gather
for every pick and a sum over the choices (tpu_dist/nn/moe.py
``_combine_rows``) the cost follows the PICKS of the call: a layer that holds
64 of 512 experts gathers 40,960 rows for a 4,096-token prefill, seven
eighths of them the appended zero row, at ~36 ns a row the largest operation
of the prefill (PERF.md, PRs 42, 46).  Every held pick owns one row of
``out``, so at most ``len(out)`` rows carry anything.  Here:

- *index work without a search loop or a scatter over the picks.*  A token's
  held picks take consecutive places ``dest`` in token order: a cumsum over
  the tokens' held counts and one over a token's ``k`` picks.  The place ->
  row map, the inverse, is read off the monotone ``ends`` by ONE compare of
  every place with every token's end (as ops/decode_attention.py's work list
  is made) and one gather of ``L`` scalars from the tokens' rows compacted to
  the front;
- *one row gather* into token order, of ``L = len(out)`` rows or, where the
  held picks fit them, of half as many (``gather_sizes``, under a ``cond``: a
  buffer sized for twice the expected share is mostly a third full, and the
  gather costs ~30 ns a row whatever it brings); whatever lies past the last
  held pick, no tile reads there;
- *a segmented sum as one kernel*: a grid step owns a tile of ``TOKENS``
  tokens, whose rows are the run ``[start[T], start[T + 1])`` of the gathered
  array (scalar prefetch).  It copies the run in chunks of ``CHUNK`` rows,
  chunk starts aligned down and the copies double-buffered, builds the
  ``(TOKENS, CHUNK)`` matrix ``w * (dest == place)`` from the tile's own
  ``dest`` and ``w``, zeroes the rows of a shared chunk that are a
  neighbouring tile's, and accumulates ``A @ chunk`` on the MXU in float32.
  The trip count is the run's, so the kernel is indifferent to how the held
  picks are spread over the tokens (bucket padding repeats one row, whose
  picks are all held or none).  No ``(k, N, d)`` array exists; the sum rounds
  once, to the activations' type.

The compare is ``L x N``: right where the picks of a call are a few tens of
thousands, which is where a share's prefill is; a caller takes this form only
where the buffer is much smaller than the picks (nn/moe.py
``_dropless_rows``).  No backward of its own: the caller's custom VJP is the
row-gather form's (the same function of ``out`` and ``w``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._pallas import (ceil_to as _ceil_to, out_struct as _out_struct,
                      sublane_tile, use_interpret as _use_interpret)
from .decode_attention import _wait

__all__ = ["combine_by_token", "gather_sizes", "token_order"]

_LANE = 128
# Tokens a grid step owns and rows a copy brings: a (128, 128) weight matrix
# is one MXU tile, a chunk of 128 rows of 2,048 bfloat16 a 512 KB copy.
TOKENS = 128
CHUNK = 128
# Columns of the model width a grid step holds (the accumulator is float32):
# the widest whole-lane divisor of the width up to this.
WIDTH_BLOCK = 2048


def gather_sizes(m_rows: int) -> tuple:
    """The places :func:`combine_by_token` may gather for a buffer of
    ``m_rows``: half of them (what a call whose held picks fit there takes)
    and all, in whole chunks."""
    return _ceil_to(-(-m_rows // 2), CHUNK), _ceil_to(m_rows, CHUNK)


def token_order(slot, m_rows: int, places: int):
    """The held picks of a call in token order.  ``slot`` ``(k, N)`` int32 is
    each pick's row of the buffer, ``m_rows`` for a pick that has none.
    Returns ``dest`` ``(N, k)`` int32, a held pick's place in token order
    (choices of one token in order; ``-1`` for a pick without a row),
    ``rows`` ``(places,)`` int32, the buffer row at each place (some row of
    the buffer past the last held pick; ``places`` >= the held picks of the
    call), and ``ends`` ``(N,)``, the places taken up to and with each
    token."""
    k, n = slot.shape
    slot_t = slot.T                                              # (N, k)
    held = slot_t < m_rows
    held_i = held.astype(jnp.int32)
    run = jnp.cumsum(held_i, axis=1)                             # (N, k)
    ends = jnp.cumsum(run[:, -1])                                # (N,)
    starts = ends - run[:, -1]
    within = run - held_i
    dest = jnp.where(held, starts[:, None] + within, -1)
    # a token's rows compacted to the front: entry i is its i-th held pick's
    compact = jnp.sum(
        jnp.where(held[:, :, None]
                  & (within[:, :, None] == jnp.arange(k, dtype=jnp.int32)),
                  slot_t[:, :, None], 0), axis=1)                # (N, k)
    # the token a place falls in and that token's first place: ends is
    # monotone, so both are reductions of one compare, no search loop
    place = jnp.arange(places, dtype=jnp.int32)
    before = ends[None, :] <= place[:, None]                     # (L, N)
    tok = jnp.sum(before, axis=1, dtype=jnp.int32)
    first = jnp.max(jnp.where(before, ends[None, :], 0), axis=1)
    at = jnp.minimum(tok * k + place - first, k * n - 1)
    return dest, compact.reshape(-1)[at], ends


def _kernel(start_ref, dest_ref, w_ref, g_hbm, y_ref, buf, sem, acc, *,
            tn, chunk, wb):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, col = pl.program_id(0), pl.program_id(1)

    @pl.when(tile >= 0)
    def _():
        lo, hi = start_ref[tile], start_ref[tile + 1]
        c0 = lo // chunk
        trips = jnp.where(hi > lo, (hi + chunk - 1) // chunk - c0, 0)
        cols = pl.ds(pl.multiple_of(col * wb, wb), wb)

        def read(i):
            rows = pl.ds(pl.multiple_of((c0 + i) * chunk, chunk), chunk)
            return pltpu.make_async_copy(g_hbm.at[rows, cols],
                                         buf.at[i % 2], sem.at[i % 2])

        @pl.when(trips > 0)
        def _():
            read(0).start()

        acc[...] = jnp.zeros(acc.shape, acc.dtype)
        k = dest_ref.shape[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (tn, chunk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

        def body(i, carry):
            _wait(read(i))

            @pl.when(i + 1 < trips)
            def _():
                read(i + 1).start()

            # place - chunk start, so that one lane index serves every chunk
            base = (c0 + i) * chunk
            at = dest_ref[...] - base                            # (tn, k)
            a = jnp.zeros((tn, chunk), jnp.float32)
            for c in range(k):
                a = jnp.where(at[:, c:c + 1] == lane, w_ref[:, c:c + 1], a)
            # rows of the chunk outside this tile's run are a neighbour's,
            # or nobody's: zeroed, so that what they hold cannot reach this
            # tile's sums through 0 x row (a row that is not finite still
            # reaches its own tile's tokens that way, and no others)
            mine = (row >= lo - base) & (row < hi - base)
            rows = jnp.where(mine, buf[i % 2], jnp.zeros((), buf.dtype))
            acc[...] += jnp.dot(a.astype(buf.dtype), rows,
                                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, trips, body, 0)
        y_ref[...] = acc[...].astype(y_ref.dtype)


def combine_by_token(out, w, slot):
    """``y (N, d) = sum_j w[j, t] * out[slot[j, t]]``: ``out`` ``(M, d)`` the
    buffer's rows, ``w`` ``(k, N)`` the picks' weights, ``slot`` ``(k, N)``
    int32 each pick's row, ``M`` for a pick that has none and adds nothing.
    Accumulated in float32, returned in ``out``'s type.  A call whose held
    picks fit half the buffer's rows gathers (and compares) that half
    alone."""
    m_rows = out.shape[0]
    k, n = slot.shape
    tn = min(TOKENS, _ceil_to(n, sublane_tile(jnp.float32)))
    pad = -(-n // tn) * tn - n
    wt = jnp.pad(w.T.astype(jnp.float32), ((0, pad), (0, 0)))

    def over(places):
        dest, rows, ends = token_order(slot, m_rows, places)
        # places past the last held pick lie in no tile's run: the kernel
        # never reads what is gathered there, so any row will do
        gathered = out.at[rows].get(mode="promise_in_bounds")
        # the places before each tile of tokens, and all of them
        start = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.pad(ends, (0, pad), mode="edge")[tn - 1::tn]])
        dest = jnp.pad(dest, ((0, pad), (0, 0)), constant_values=-1)
        return _call(start, dest, wt, gathered, tn=tn,
                     interpret=_use_interpret())[:n]

    half, whole = gather_sizes(m_rows)
    if half == whole:
        return over(whole)
    return jax.lax.cond(jnp.sum(slot < m_rows) <= half,
                        lambda: over(half), lambda: over(whole))


# jitted so that a model's layers share ONE trace and ONE Mosaic lowering
@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def _call(start, dest, wt, gathered, *, tn, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    np_, k = dest.shape
    d = gathered.shape[1]
    fit = [b for b in range(_LANE, min(d, WIDTH_BLOCK) + 1, _LANE)
           if d % b == 0]
    wb = max(fit) if fit else d
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(np_ // tn, d // wb),
        in_specs=[pl.BlockSpec((tn, k), lambda t, c, s: (t, 0)),
                  pl.BlockSpec((tn, k), lambda t, c, s: (t, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tn, wb), lambda t, c, s: (t, c)),
        scratch_shapes=[pltpu.VMEM((2, CHUNK, wb), gathered.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((tn, wb), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, tn=tn, chunk=CHUNK, wb=wb),
        grid_spec=grid_spec,
        out_shape=_out_struct((np_, d), gathered.dtype, dest, wt, gathered),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="moe_combine",
    )(start, dest, wt, gathered)

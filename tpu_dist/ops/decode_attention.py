"""Slot-decode attention — a Pallas TPU kernel over the resident K/V only.

One decode step of continuous batching attends ONE new position per slot
over a K/V pool stored ``(B, Hkv, D, Tmax)``, time last
(:meth:`tpu_dist.nn.MultiheadSelfAttention.init_cache`).  The dense form
(``nn/attention.py`` ``_decode``) selects the new column into the whole
pool and reduces over all of it, so every layer of every step reads and
rewrites ``2 x B x Hkv x D x Tmax`` elements whatever the slots hold.  This
kernel makes the step's traffic follow the occupancy:

- the slots' lengths arrive as scalar-prefetch operands and become a work
  list, one entry per (busy slot, time block) with the blocks ``[0,
  ceil((len + 1) / block))`` of that slot.  ONE kernel invocation loops
  over the list, bounded by its length, and copies each entry's K and V
  block HBM -> VMEM by hand through a ring of three buffers (two copies in
  flight while one block is computed on): a free slot (length 0) and the
  blocks past a slot's last cost no HBM read and no loop trip;
- scores, the softmax statistics and the weighted values are accumulated
  in float32.  With ONE query row a K/V head (``H == Hkv``) lane by lane
  (each of the 128 lanes keeps its own running maximum, sum and weighted
  values; one cross-lane combine per slot), on the VPU: there is nothing
  for the MXU (a form that used it timed the same, PERF.md PR 26: the
  copies set the pace).  With GROUPED queries (``H = G x Hkv``, ``G`` > 1,
  read from the operands' shapes: :func:`_grouped_kernel`) a K/V head's
  block is copied ONCE for the ``G`` query rows it serves (query head ``j
  * G + g`` is served by K/V head ``j``, as the dense branch's reshape has
  it) and the rows are one MXU operand: ``(G', D) x (D, block)`` for the
  scores and ``(G', block) x (block, D)`` for the values a head, ``G'`` =
  ``G`` padded to whole sublane tiles, the pool's type on the operands,
  the statistics one ``(G', 1)`` column a head; the new column joins its
  block in VMEM and is attended as one of its columns;
- the new K and V column is written IN PLACE through
  ``input_output_aliases``: the only thing written back per slot and
  tensor is the ``(Hkv, D, 128)`` slab that holds column ``len``.  A
  column at ``Tmax`` is dropped, as ``_write_columns`` drops it.

A free slot is not visited: its pool row is untouched and its output row
is zero.  Float pools only; the int8 cache's hoisted scales are a
different kernel and stay on the dense branch (it has no grouped-query
form: ``init_cache`` refuses it).

Another form, :func:`latent_decode_attention`, serves a LATENT pool
``(B, C, Tmax)`` with no head axis (tpu_dist/nn/mla.py, the absorbed
path): every head's query row against the SAME columns, the values the
first ``value_dim`` rows of those columns.  Same work list, same ring of
hand-made copies, same in-place write of the slab that holds the new
column; with ``H`` query rows a block the scores and the weighted values
are two MXU matmuls a block, ``(H, C) x (C, block)`` and ``(H, block) x
(block, value_dim)``, and the softmax statistics are one ``(H, 1)`` column
each.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ._pallas import (ceil_to as _ceil_to, out_struct as _out_struct,
                      sublane_tile, use_interpret as _use_interpret)

__all__ = ["decode_attention", "decode_attention_ok",
           "latent_decode_attention", "kv_blocks"]

_LANE = 128
_NEG = -1e30   # finite: a lane that has seen no column yet stays NaN-free
_RING = 3      # K/V block buffers: two copies in flight, one computed on
# Time columns a block holds.  Timed on the chip (PERF.md, PR 26): 128
# doubles the trips, 512 and 1024 read up to a quarter more of a row than
# the slot holds.  The grouped form the same (PERF.md, PR 45; 20 over 4 heads
# of 128, 80 slots of ~590 columns: 128 columns 0.277 ms, 256 0.222, 512
# 0.250, 1,024 0.272; 16 over 2 of 256, 96 slots of ~3,100: 256 0.983, 512
# 0.992, 1,024 1.022): its copies set the pace too, the MXU's work hides.
BLOCK_K = 256


def _wait(copy) -> None:
    """Block on a copy's DMA semaphore, inside a kernel: there is no peer
    that could die and no deadline to pass."""
    # tpudlint: disable=TD004  # a DMA semaphore inside a kernel, no peer
    copy.wait()


# The latent form's block: 576 numbers a column make a 256-column copy
# 295 KB, too small to hide a copy's start-up.  Timed on the chip (PERF.md,
# PR 32; 128 slots of ~1,950 columns, one layer): 128 columns 1.19 ms, 256
# 0.78, 512 0.58 (kept), 1,024 0.61; a ring of 4 buffers changed nothing.
LATENT_BLOCK_K = 512


def _block_k(tmax: int, block: int = BLOCK_K) -> int:
    return block if tmax % block == 0 else _LANE


def decode_attention_ok(pool) -> bool:
    """Whether the kernel takes this K/V pool leaf: a float one whose ``D``
    fills whole sublane tiles and whose ``Tmax`` fills whole lanes."""
    return (jnp.issubdtype(pool.dtype, jnp.floating)
            and pool.shape[-2] % sublane_tile(pool.dtype) == 0
            and pool.shape[-1] % _LANE == 0)


def kv_blocks(lengths, tmax: int):
    """``(blocks read, blocks in the pool, block)`` of one decode step:
    a busy slot reads ``ceil((len + 1) / block)`` time blocks, clipped to
    the row; a free one (length 0) none.  Host arithmetic on a numpy
    vector — the engine's counter (``SlotEngine.stats()["decode_attn"]``)
    and the kernel's own work list (:func:`_work_list`) count alike.  (The
    latent form copies blocks of ``LATENT_BLOCK_K`` columns: at most one
    of these blocks more a slot than counted here.)"""
    tk = _block_k(tmax)
    n = _blocks_per_slot(lengths, tmax, tk)
    return int(n.sum()), len(lengths) * -(-tmax // tk), tk


def _blocks_per_slot(lengths, tmax, tk):
    """``ceil((len + 1) / tk)`` clipped to the row, 0 for a free slot;
    numpy and jax vectors alike."""
    return (lengths > 0) * ((lengths + tk) // tk).clip(max=-(-tmax // tk))


def _work_list(lengths, tmax, tk):
    """The scalar-prefetch vector and ``G = B * Tmax / tk``, the most
    entries a list can hold: ``slot[G]`` and ``block[G]`` of each entry
    (busy slots in order, a slot's blocks in order; entries past the
    ``total`` are never read), then the ``B`` lengths, then ``total``."""
    b = lengths.shape[0]
    g = b * (tmax // tk)
    n = _blocks_per_slot(lengths, tmax, tk).astype(jnp.int32)
    ends = jnp.cumsum(n)
    step = jnp.arange(g, dtype=jnp.int32)
    # the slot whose blocks end past this entry (one compare, no search loop)
    slot = jnp.minimum(jnp.sum(ends[None, :] <= step[:, None], axis=1,
                               dtype=jnp.int32), b - 1)
    blk = step - (ends - n)[slot]
    return jnp.concatenate([slot, blk, lengths, ends[-1:]]), g


def _kv_copies(s_ref, g, tk, pools, rings, rsem, slabs, outs, wsem):
    """The hand-made copies both K/V kernels share, as two functions:
    ``reads(i)``, the K and V copies of work-list entry ``i``'s block of
    ``tk`` columns into its ring buffer, and ``writes(slot, col)``, the
    copies of the new column's slabs back into the pools at ``col``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def reads(i):
        buf = i % _RING
        cols = pl.ds(pl.multiple_of(s_ref[g + i] * tk, tk), tk)
        return [pltpu.make_async_copy(hbm.at[s_ref[i], :, :, cols],
                                      ring.at[buf], rsem.at[j, buf])
                for j, (hbm, ring) in enumerate(zip(pools, rings))]

    def writes(slot, col):
        cols = pl.ds(pl.multiple_of(col, _LANE), _LANE)
        return [pltpu.make_async_copy(w, hbm.at[slot, :, :, cols],
                                      wsem.at[j])
                for j, (w, hbm) in enumerate(zip(slabs, outs))]

    return reads, writes


def _kernel(s_ref, x_ref, k_hbm, v_hbm, o_ref, ko_hbm, vo_hbm,
            kbuf, vbuf, wk, wv, rsem, wsem, xb_ref, m_ref, l_ref, acc_ref,
            *, g, tk, tmax, scale):
    from jax.experimental import pallas as pl

    slots, _, d, heads = x_ref.shape
    total = s_ref[2 * g + slots]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANE), 2)
    f32 = jnp.float32
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)     # free slots' rows

    reads, writes = _kv_copies(s_ref, g, tk, (k_hbm, v_hbm), (kbuf, vbuf),
                               rsem, (wk, wv), (ko_hbm, vo_hbm), wsem)

    for j in range(_RING - 1):
        @pl.when(j < total)
        def _():
            for copy in reads(j):
                copy.start()

    def entry(i, writing):
        slot, blk = s_ref[i], s_ref[g + i]
        ln = s_ref[2 * g + slot]
        for copy in reads(i):
            _wait(copy)

        @pl.when(i + _RING - 1 < total)
        def _():
            for copy in reads(i + _RING - 1):
                copy.start()

        k_ref, v_ref = kbuf.at[i % _RING], vbuf.at[i % _RING]

        @pl.when(blk == 0)
        def _():
            # a slot's first block: q and the new K/V column, each (D, H)
            # with the heads in the lanes, spread to (H, D, 128)
            # lane-replicated; the new column opens the softmax (lane 0)
            x = x_ref[slot].astype(f32)
            for h in range(heads):
                for c in range(3):
                    xb_ref[c, h] = jnp.broadcast_to(x[c][:, h:h + 1],
                                                    (d, _LANE))
            has_new = (ln < tmax) & (lane == 0)
            s_new = jnp.sum(xb_ref[0] * xb_ref[1], axis=1,
                            keepdims=True) * scale
            m_ref[...] = jnp.where(has_new, s_new, _NEG)
            l_ref[...] = jnp.broadcast_to(jnp.where(has_new, 1.0, 0.0),
                                          l_ref.shape)
            acc_ref[...] = jnp.where(has_new, xb_ref[2], 0.0)

        @pl.when(blk * tk < ln)
        def _():
            q = xb_ref[0]
            for c in range(tk // _LANE):
                cols = slice(c * _LANE, (c + 1) * _LANE)
                s = jnp.sum(q * k_ref[:, :, cols].astype(f32), axis=1,
                            keepdims=True) * scale             # (H, 1, 128)
                seen = blk * tk + c * _LANE + lane < ln
                m_prev = m_ref[...]
                m_new = jnp.maximum(m_prev, jnp.where(seen, s, _NEG))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
                m_ref[...] = m_new
                l_ref[...] = alpha * l_ref[...] + p
                acc_ref[...] = (alpha * acc_ref[...]
                                + p * v_ref[:, :, cols].astype(f32))

        # the slot's last block holds column ``len`` (a column at Tmax:
        # none, nothing to write)
        is_last = blk == jnp.minimum((ln + tk) // tk, tmax // tk) - 1
        has_col = ln < tmax

        @pl.when(is_last)
        def _():
            @pl.when(writing == 1)
            def _():                      # the slabs are free again
                for copy in writes(0, 0):
                    _wait(copy)

            @pl.when(has_col)
            def _():
                at = ln - blk * tk
                cols = pl.ds(pl.multiple_of(at // _LANE * _LANE, _LANE),
                             _LANE)
                here = lane == at % _LANE
                wk[...] = jnp.where(here, xb_ref[1], k_ref[:, :, cols].astype(
                    f32)).astype(wk.dtype)
                wv[...] = jnp.where(here, xb_ref[2], v_ref[:, :, cols].astype(
                    f32)).astype(wv.dtype)
                for copy in writes(slot, ln // _LANE * _LANE):
                    copy.start()

            # combine the 128 lanes' partial softmaxes
            m = m_ref[...]
            w = jnp.exp(m - jnp.max(m, axis=2, keepdims=True))
            denom = jnp.sum(l_ref[...] * w, axis=2, keepdims=True)
            o = jnp.sum(acc_ref[...] * w, axis=2, keepdims=True) / denom
            head = jax.lax.broadcasted_iota(jnp.int32, (d, heads), 1)
            out = jnp.zeros((d, heads), f32)
            for h in range(heads):
                out = jnp.where(head == h, o[h], out)          # (D, H)
            o_ref[slot] = out.astype(o_ref.dtype)

        return jnp.where(is_last, has_col.astype(jnp.int32), writing)

    writing = jax.lax.fori_loop(0, total, entry, jnp.int32(0))

    @pl.when(writing == 1)
    def _():
        for copy in writes(0, 0):
            _wait(copy)


def decode_attention(q, k_new, v_new, k_pool, v_pool, lengths):
    """One new position per slot against a time-last K/V pool.

    ``q``: ``(B, H, D)``, this step's query; ``k_new``, ``v_new``: ``(B,
    Hkv, D)``, the column to append; ``k_pool``, ``v_pool``: ``(B, Hkv, D,
    Tmax)``, with ``H = G x Hkv``: ``G`` = 1 takes the one-row form, ``G`` >
    1 the grouped one (nothing else chooses); ``lengths``: ``(B,)`` int,
    the positions resident in each slot = the column the new one lands in.
    Returns ``(out (B, H, D) in q.dtype,
    k_pool, v_pool)`` with the column written (the pools are aliased to
    the results: donate them).  Slot ``b`` attends columns ``<= len[b]``,
    its own new one included, exactly as the dense branch masks; a slot of
    length 0 is FREE (``TransformerLM.decode_step``'s convention): not
    read, not written, output zero."""
    if q.shape[1] != k_pool.shape[1]:
        return _grouped_call(q, k_new, v_new, k_pool, v_pool, lengths,
                             interpret=_use_interpret())
    return _call(q, k_new, v_new, k_pool, v_pool, lengths,
                 interpret=_use_interpret())


# jitted so that a model's layers share ONE trace and ONE Mosaic lowering:
# unjitted, 48 layers cost 15 s of lowering at every process start
@functools.partial(jax.jit, static_argnames="interpret")
def _call(q, k_new, v_new, k_pool, v_pool, lengths, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, d, tmax = k_pool.shape
    tk = _block_k(tmax)
    scalars, g = _work_list(jnp.asarray(lengths, jnp.int32), tmax, tk)
    # q and the new column as (B, 3, D, H): D in the sublanes as the pool
    # has it, heads in the lanes
    cdt = jnp.promote_types(q.dtype, k_pool.dtype)
    x = jnp.stack([q.astype(cdt), k_new.astype(k_pool.dtype).astype(cdt),
                   v_new.astype(v_pool.dtype).astype(cdt)], axis=1)
    x = jnp.swapaxes(x, 2, 3)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, s: (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    small = (heads, d, _LANE)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[whole(*x.shape), in_hbm, in_hbm],
        out_specs=[whole(b, d, heads), in_hbm, in_hbm],
        scratch_shapes=[pltpu.VMEM((_RING, heads, d, tk), k_pool.dtype),
                        pltpu.VMEM((_RING, heads, d, tk), v_pool.dtype),
                        pltpu.VMEM(small, k_pool.dtype),
                        pltpu.VMEM(small, v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, _RING)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((3,) + small, jnp.float32),
                        pltpu.VMEM((heads, 1, _LANE), jnp.float32),
                        pltpu.VMEM((heads, 1, _LANE), jnp.float32),
                        pltpu.VMEM(small, jnp.float32)])
    out, k_pool, v_pool = pl.pallas_call(
        functools.partial(_kernel, g=g, tk=tk, tmax=tmax,
                          scale=1.0 / math.sqrt(d)),
        grid_spec=grid_spec,
        out_shape=[_out_struct((b, d, heads), q.dtype, x, k_pool, v_pool),
                   _out_struct(k_pool.shape, k_pool.dtype, x, k_pool, v_pool),
                   _out_struct(v_pool.shape, v_pool.dtype, x, k_pool,
                               v_pool)],
        # operands count the scalar-prefetch vector: 2 and 3 are the pools
        input_output_aliases={2: 1, 3: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="decode_attention",
    )(scalars, x, k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2), k_pool, v_pool


# -- the grouped-query form ---------------------------------------------------

def _grouped_kernel(s_ref, q_ref, new_ref, k_hbm, v_hbm, o_ref, ko_hbm,
                    vo_hbm, kbuf, vbuf, wk, wv, rsem, wsem, m_ref, l_ref,
                    acc_ref, *, g, tk, tmax, scale):
    from jax.experimental import pallas as pl

    slots, heads = q_ref.shape[:2]
    total = s_ref[2 * g + slots]
    f32 = jnp.float32
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)     # free slots' rows

    # ONE copy of a K/V head's block for all the query rows it serves
    reads, writes = _kv_copies(s_ref, g, tk, (k_hbm, v_hbm), (kbuf, vbuf),
                               rsem, (wk, wv), (ko_hbm, vo_hbm), wsem)

    for j in range(_RING - 1):
        @pl.when(j < total)
        def _():
            for copy in reads(j):
                copy.start()

    def entry(i, writing):
        slot, blk = s_ref[i], s_ref[g + i]
        ln = s_ref[2 * g + slot]
        for copy in reads(i):
            _wait(copy)

        @pl.when(i + _RING - 1 < total)
        def _():
            for copy in reads(i + _RING - 1):
                copy.start()

        k_ref, v_ref = kbuf.at[i % _RING], vbuf.at[i % _RING]

        @pl.when(blk == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, _NEG, f32)
            l_ref[...] = jnp.zeros(l_ref.shape, f32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        # the slot's last block holds column ``len`` (a column at Tmax:
        # none, nothing to write): the new K and V columns join their
        # blocks in VMEM, are attended as one of their columns, and the
        # slabs go back
        is_last = blk == jnp.minimum((ln + tk) // tk, tmax // tk) - 1
        has_col = ln < tmax

        @pl.when(is_last & (writing == 1))
        def _():                          # the slabs are free again
            for copy in writes(0, 0):
                _wait(copy)

        @pl.when(is_last & has_col)
        def _():
            at = ln - blk * tk
            cols = pl.ds(pl.multiple_of(at // _LANE * _LANE, _LANE), _LANE)
            # this slot's columns out of (D, B), B in the lanes, spread
            # over 128 lanes: one small matmul a head with a one-hot of
            # the slot
            pick = (jax.lax.broadcasted_iota(jnp.int32, (new_ref.shape[-1],
                                                         _LANE), 0)
                    == slot).astype(new_ref.dtype)
            here = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE),
                                            1) == at % _LANE
            for c, (ring, slab) in enumerate(((k_ref, wk), (v_ref, wv))):
                for h in range(heads):
                    col = jnp.dot(new_ref[c, h], pick,
                                  preferred_element_type=f32)
                    slab[h] = jnp.where(here, col, ring[h, :, cols].astype(
                        f32)).astype(slab.dtype)
                ring[:, :, cols] = slab[...]
            for copy in writes(slot, ln // _LANE * _LANE):
                copy.start()

        col_id = blk * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        seen = col_id < ln + has_col.astype(jnp.int32)
        for h in range(heads):
            # query heads [h * G, (h + 1) * G) against K/V head h
            s = jnp.dot(q_ref[slot, h], k_ref[h],
                        preferred_element_type=f32) * scale   # (G', tk)
            s = jnp.where(seen, s, _NEG)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            m_ref[h] = m_new
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=f32)                   # (G', D)

        @pl.when(is_last)
        def _():
            o_ref[slot] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return jnp.where(is_last, has_col.astype(jnp.int32), writing)

    writing = jax.lax.fori_loop(0, total, entry, jnp.int32(0))

    @pl.when(writing == 1)
    def _():
        for copy in writes(0, 0):
            _wait(copy)


@functools.partial(jax.jit, static_argnames="interpret")
def _grouped_call(q, k_new, v_new, k_pool, v_pool, lengths, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, d, tmax = k_pool.shape
    tk = _block_k(tmax)
    group = q.shape[1] // heads
    rows = _ceil_to(group, sublane_tile(k_pool.dtype))
    scalars, g = _work_list(jnp.asarray(lengths, jnp.int32), tmax, tk)
    # a K/V head's query rows as one (G', D) operand of the MXU, in the
    # pool's type, G padded to whole sublane tiles with rows of zeros
    qg = jnp.pad(q.astype(k_pool.dtype).reshape(b, heads, group, d),
                 ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    # the new columns as (2, Hkv, D, B'): D in the sublanes as the pool has
    # it, the slots in the lanes, padded to whole lane tiles
    new = jnp.stack([k_new.astype(k_pool.dtype), v_new.astype(v_pool.dtype)])
    new = jnp.pad(jnp.moveaxis(new, 1, -1),
                  ((0, 0), (0, 0), (0, 0), (0, -b % _LANE)))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, s: (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    slab = (heads, d, _LANE)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[whole(*qg.shape), whole(*new.shape), in_hbm, in_hbm],
        out_specs=[whole(*qg.shape), in_hbm, in_hbm],
        scratch_shapes=[pltpu.VMEM((_RING, heads, d, tk), k_pool.dtype),
                        pltpu.VMEM((_RING, heads, d, tk), v_pool.dtype),
                        pltpu.VMEM(slab, k_pool.dtype),
                        pltpu.VMEM(slab, v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, _RING)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((heads, rows, 1), jnp.float32),
                        pltpu.VMEM((heads, rows, 1), jnp.float32),
                        pltpu.VMEM((heads, rows, d), jnp.float32)])
    operands = (qg, new, k_pool, v_pool)
    out, k_pool, v_pool = pl.pallas_call(
        functools.partial(_grouped_kernel, g=g, tk=tk, tmax=tmax,
                          scale=1.0 / math.sqrt(d)),
        grid_spec=grid_spec,
        out_shape=[_out_struct(qg.shape, q.dtype, *operands),
                   _out_struct(k_pool.shape, k_pool.dtype, *operands),
                   _out_struct(v_pool.shape, v_pool.dtype, *operands)],
        # operands count the scalar-prefetch vector: 3 and 4 are the pools
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="decode_attention",
    )(scalars, *operands)
    return out[:, :, :group].reshape(q.shape), k_pool, v_pool


# -- the latent form ----------------------------------------------------------

def _latent_kernel(s_ref, q_ref, new_ref, pool_hbm, o_ref, out_hbm,
                   buf, slab, rsem, wsem, m_ref, l_ref, acc_ref,
                   *, g, tk, tmax, r, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots = q_ref.shape[0]
    total = s_ref[2 * g + slots]
    f32 = jnp.float32
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)     # free slots' rows

    def read(i):
        """The copy of entry ``i``'s block of columns into its buffer."""
        cols = pl.ds(pl.multiple_of(s_ref[g + i] * tk, tk), tk)
        return pltpu.make_async_copy(pool_hbm.at[s_ref[i], :, cols],
                                     buf.at[i % _RING], rsem.at[i % _RING])

    def write(slot, col):
        """The copy of the new column's slab back into the pool."""
        cols = pl.ds(pl.multiple_of(col, _LANE), _LANE)
        return pltpu.make_async_copy(slab, out_hbm.at[slot, :, cols],
                                     wsem.at[0])

    for j in range(_RING - 1):
        @pl.when(j < total)
        def _():
            read(j).start()

    def entry(i, writing):
        slot, blk = s_ref[i], s_ref[g + i]
        ln = s_ref[2 * g + slot]
        _wait(read(i))

        @pl.when(i + _RING - 1 < total)
        def _():
            read(i + _RING - 1).start()

        block = buf.at[i % _RING]

        @pl.when(blk == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, _NEG, f32)
            l_ref[...] = jnp.zeros(l_ref.shape, f32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

        # the slot's last block holds column ``len`` (a column at Tmax:
        # none, nothing to write): the new column joins the block in VMEM,
        # is attended as one of its columns, and its slab goes back
        is_last = blk == jnp.minimum((ln + tk) // tk, tmax // tk) - 1
        has_col = ln < tmax

        @pl.when(is_last & (writing == 1))
        def _():                          # the slab is free again
            _wait(write(0, 0))

        @pl.when(is_last & has_col)
        def _():
            at = ln - blk * tk
            cols = pl.ds(pl.multiple_of(at // _LANE * _LANE, _LANE), _LANE)
            # this slot's column out of (C, B), B in the lanes, spread over
            # 128 lanes: one small matmul with a one-hot of the slot
            pick = (jax.lax.broadcasted_iota(jnp.int32, (new_ref.shape[1],
                                                         _LANE), 0)
                    == slot).astype(new_ref.dtype)
            col = jnp.dot(new_ref[...], pick, preferred_element_type=f32)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
            slab[...] = jnp.where(lane == at % _LANE, col,
                                  block[:, cols].astype(f32)
                                  ).astype(slab.dtype)
            block[:, cols] = slab[...]
            write(slot, ln // _LANE * _LANE).start()

        q = q_ref[slot]                                       # (H, C)
        s = jnp.dot(q, block[...], preferred_element_type=f32) * scale
        col_id = blk * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        seen = col_id < ln + has_col.astype(jnp.int32)
        s = jnp.where(seen, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)          # (H, tk)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(block.dtype), block[:r, :], (((1,), (1,)), ((), ())),
            preferred_element_type=f32)                       # (H, r)

        @pl.when(is_last)
        def _():
            o_ref[slot] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return jnp.where(is_last, has_col.astype(jnp.int32), writing)

    writing = jax.lax.fori_loop(0, total, entry, jnp.int32(0))

    @pl.when(writing == 1)
    def _():
        _wait(write(0, 0))


def latent_decode_attention(q, new, pool, lengths, *, value_dim: int,
                            scale: float):
    """One new position per slot against a time-last LATENT pool.

    ``q``: ``(B, H, C)``, every head's query row over the latent's ``C``
    numbers; ``new``: ``(B, C)``, the column to append; ``pool``: ``(B, C,
    Tmax)``; ``lengths``: ``(B,)`` int, the positions resident in each
    slot = the column the new one lands in.  Scores are ``scale * q .
    column``; the values are the first ``value_dim`` numbers of each
    column.  Returns ``(out (B, H, value_dim) in q.dtype, pool)`` with the
    column written (the pool is aliased to the result: donate it).  Slot
    ``b`` attends columns ``<= len[b]``, its own new one included; a slot
    of length 0 is FREE: not read, not written, output zero."""
    return _latent_call(q, new, pool, lengths, value_dim=value_dim,
                        scale=float(scale), interpret=_use_interpret())


@functools.partial(jax.jit,
                   static_argnames=("value_dim", "scale", "interpret"))
def _latent_call(q, new, pool, lengths, *, value_dim, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, tmax = pool.shape
    heads = q.shape[1]
    tk = _block_k(tmax, LATENT_BLOCK_K)
    scalars, g = _work_list(jnp.asarray(lengths, jnp.int32), tmax, tk)
    q = q.astype(pool.dtype)
    # the new columns as (C, B'): C in the sublanes as the pool has it, the
    # slots in the lanes, padded to whole lane tiles
    new_t = jnp.pad(new.astype(pool.dtype).T, ((0, 0), (0, -b % _LANE)))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, s: (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[whole(*q.shape), whole(*new_t.shape), in_hbm],
        out_specs=[whole(b, heads, value_dim), in_hbm],
        scratch_shapes=[pltpu.VMEM((_RING, c, tk), pool.dtype),
                        pltpu.VMEM((c, _LANE), pool.dtype),
                        pltpu.SemaphoreType.DMA((_RING,)),
                        pltpu.SemaphoreType.DMA((1,)),
                        pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, value_dim), jnp.float32)])
    out, pool = pl.pallas_call(
        functools.partial(_latent_kernel, g=g, tk=tk, tmax=tmax,
                          r=value_dim, scale=scale),
        grid_spec=grid_spec,
        out_shape=[_out_struct((b, heads, value_dim), q.dtype, q, new_t,
                               pool),
                   _out_struct(pool.shape, pool.dtype, q, new_t, pool)],
        # operands count the scalar-prefetch vector: 3 is the pool
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="latent_decode_attention",
    )(scalars, q, new_t, pool)
    return out, pool

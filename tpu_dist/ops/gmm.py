"""Grouped matrix multiply — the dropless-MoE Pallas kernel pair.

MegaBlocks-style block-diagonal expert compute (no reference counterpart:
the reference is a 486-line data-parallel image tutorial; this backs the
beyond-parity MoE substrate, nn/moe.py ``dispatch="dropless"``).

The GShard capacity formulation pads every expert to ``C = ceil(k*N/E *
capacity_factor)`` slots, burning ``capacity_factor - 1`` of the expert-FFN
FLOPs on padding (and dropping tokens when an expert overflows).  Dropless
routing instead SORTS the (choice, token) rows by expert and runs each
expert over its exact contiguous segment, padded only to the row-block
size:

    x (M, D) sorted by expert, block-aligned segments
    w (E, D, H) stacked expert weights
    out[rows of expert e] = x[rows of e] @ w[e]

``gmm`` computes that with a (row_blocks, h_tiles) grid: each row block
carries a single expert id, delivered to the weight BlockSpec's index_map
through Pallas TPU **scalar prefetch** (the map is data-dependent — exactly
what PrefetchScalarGridSpec exists for).  Row blocks past the live count
(the block-alignment overallocation tail) skip the MXU entirely and write
zeros.  ``tgmm`` is the transposed pass (dw[e] = x_e^T @ dy_e) with the
row-block sweep INNERMOST so each expert's f32 accumulator tile stays in
VMEM scratch across its segment — group boundaries, also from the
prefetched map, zero and flush it.

Only forward primitives live here; nn/moe.py composes them into the
dropless dispatch and wires the custom VJP (dx via gmm against w^T, dw/db
via tgmm — all three backward passes are themselves grouped matmuls over
the same block map, no scatters anywhere).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._pallas import (ceil_to as _ceil_to, out_struct as _out_struct,
                      use_interpret as _use_interpret)

__all__ = ["gmm", "tgmm", "grouped_linear"]

_LANE = 128
_VMEM_BUDGET = 12 * 1024 * 1024  # leave headroom under the ~16MB scoped vmem


def _fit_blocks(block_rows: int, block_h: int, dp: int, itemsize: int,
                scratch_rows: int = 0) -> tuple[int, int]:
    """Shrink tile sizes until the double-buffered working set fits VMEM.

    The default 512x512 tiles with a wide contraction dim (e.g. the dx
    pass against a 3072-wide hidden) blow the ~16MB scoped-vmem stack;
    estimate ≈ 2x(x_tile + w_tile + out_tile) + f32 accumulator(s) and
    halve the larger tile dim until it fits (floor 128)."""
    def need(br, bh):
        tiles = (br * dp + dp * bh + br * bh) * itemsize * 2
        acc = (br * bh + scratch_rows * bh) * 4
        return tiles + acc

    while need(block_rows, block_h) > _VMEM_BUDGET and (
            block_rows > 128 or block_h > 128):
        if block_rows >= block_h and block_rows > 128:
            block_rows //= 2
        elif block_h > 128:
            block_h //= 2
        else:
            break
    return block_rows, block_h


def _dividing_tile(width: int, tile: int) -> int:
    """``tile`` where it divides ``width`` (whole lanes), else the widest
    multiple of ``_LANE`` under it that does; a tile under a lane (a test's)
    has none and stays."""
    if width % tile == 0:
        return tile
    return max((t for t in range(_LANE, tile, _LANE) if width % t == 0),
               default=tile)


def gmm(x, w, block_groups, n_live_blocks, *, bias=None, block_rows: int = 512,
        block_h: int = 512, out_dtype=None, activation=None,
        name: str = "gmm"):
    """Block-diagonal grouped matmul: ``out[i*B:(i+1)*B] = x[i*B:(i+1)*B]
    @ w[block_groups[i]] (+ bias[block_groups[i]])``.

    Args:
        x: (M, D) rows sorted by group, M a multiple of ``block_rows``.
        w: (E, D, H) stacked per-group weights.
        block_groups: (M // block_rows,) int32 group id per row block —
            every row in a block must belong to that group (nn/moe.py's
            sort pads each group's segment to a block multiple).
        n_live_blocks: scalar int32; blocks at index >= this are the
            overallocation tail — skipped on the MXU, written as zeros.
        bias: optional (E, H) per-group bias, added in-kernel.
        block_rows / block_h: VMEM tile sizes (D is kept whole).
        activation: optional elementwise fn applied in-kernel on the f32
            accumulator (e.g. ``jax.nn.gelu``) — saves a full (M, H) HBM
            round-trip vs applying it outside.
        name: the ``pallas_call``'s name, which a device trace shows as
            the operation's; must hold ``gmm`` (chipbench finds the
            kernels by it).
    Returns:
        (M, H) in ``out_dtype`` (default ``x.dtype``).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = x.shape
    e, dw_, h = w.shape
    if dw_ != d:
        raise ValueError(f"w contraction dim {dw_} != x dim {d}")
    if m % block_rows:
        raise ValueError(f"M={m} not a multiple of block_rows={block_rows}")
    out_dtype = out_dtype or x.dtype
    dp = _ceil_to(d, _LANE)
    block_h = min(block_h, _ceil_to(h, _LANE))
    br = block_rows
    block_rows, block_h = _fit_blocks(block_rows, block_h, dp,
                                      jnp.dtype(x.dtype).itemsize)
    # H is padded up to whole tiles, and padding it copies the WHOLE weight
    # stack at every call (a width of 2,304 under tiles of 512: 32 experts'
    # 151 MB read and 168 written a layer a decode step, a fifth of the
    # step; PERF.md, PR 40): take the widest tile of whole lanes that
    # divides the lane-padded width.  A width of whole tiles keeps its tile.
    block_h = _dividing_tile(_ceil_to(h, _LANE), block_h)
    if block_rows != br:
        # each caller row-block split into equal sub-blocks: expand the
        # block->group map and live count to the finer granularity
        f = br // block_rows
        block_groups = jnp.repeat(block_groups, f)
        n_live_blocks = n_live_blocks * f
    nb = m // block_rows
    hp = _ceil_to(h, block_h)
    xp = jnp.pad(x, ((0, 0), (0, dp - d)))
    wp = jnp.pad(w, ((0, 0), (0, dp - d), (0, hp - h)))
    has_bias = bias is not None
    # (E, 1, Hp): the singleton middle axis keeps the block's last-two
    # dims legal for Mosaic ((1, block_h) blocks of a 2-D (E, H) array
    # are rejected — second-to-last dim must be 8-divisible or whole)
    bp = (jnp.pad(bias, ((0, 0), (0, hp - h)))[:, None, :]
          if has_bias else jnp.zeros((e, 1, block_h), w.dtype))
    scalars = jnp.concatenate(
        [block_groups.astype(jnp.int32),
         jnp.full((1,), n_live_blocks, jnp.int32)])

    def kernel(scalar_ref, x_ref, w_ref, b_ref, o_ref):
        i = pl.program_id(1)  # row-block index (INNER — see grid note)
        live = i < scalar_ref[nb]

        @pl.when(live)
        def _():
            acc = jax.lax.dot_general(
                x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if has_bias:
                acc = acc + b_ref[0, 0].astype(jnp.float32)
            if activation is not None:
                acc = activation(acc)
            o_ref[...] = acc.astype(o_ref.dtype)

        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # Grid order matters for HBM traffic: the row sweep must be INNER so
    # the weight BlockSpec index (s[i], j) stays constant across each
    # group's contiguous row blocks and Pallas keeps the tile resident —
    # w is then DMA'd once per h-tile sweep (= once total).  Rows outer
    # re-fetched the ENTIRE weight tensor per row block (~nb x |w|, the
    # measured ~2.4 ms floor at GPT-2-small MoE shapes); x re-reads per
    # h-tile are the cheaper side of that trade (|x| << nb x |w|).
    # The no-bias placeholder is (E, 1, block_h) — a single h-block — so its
    # index_map must pin j to 0 rather than lean on Pallas' out-of-bounds
    # block-index clamping (never read, but fragile against bounds-checking
    # changes).
    bias_index = ((lambda j, i, s: (s[i], 0, j)) if has_bias
                  else (lambda j, i, s: (s[i], 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hp // block_h, nb),
        in_specs=[
            pl.BlockSpec((block_rows, dp), lambda j, i, s: (i, 0)),
            pl.BlockSpec((1, dp, block_h), lambda j, i, s: (s[i], 0, j)),
            pl.BlockSpec((1, 1, block_h), bias_index),
        ],
        out_specs=pl.BlockSpec((block_rows, block_h),
                               lambda j, i, s: (i, j)),
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=_out_struct((m, hp), out_dtype, xp, wp, bp),
        interpret=_use_interpret(),
        name=name,
    )(scalars, xp, wp, bp)
    return out[:, :h]


def tgmm(x, dy, block_groups, n_groups: int, *, block_rows: int = 512,
         block_h: int = 512, with_rowsum: bool = False, out_dtype=None):
    """Transposed grouped matmul: ``dw[e] = sum over e's row blocks of
    x_block^T @ dy_block`` (+ optionally ``db[e] = sum of dy rows``).

    The grid is (h_tiles, row_blocks) — row sweep INNERMOST so each
    group's (D, block_h) f32 accumulator persists in VMEM scratch across
    its contiguous segment; the prefetched ``block_groups`` map marks the
    boundaries.  Overallocation-tail blocks must carry the last live
    group's id with all-zero rows (nn/moe.py guarantees both), so they
    accumulate nothing and the final flush still fires at the grid edge.
    Groups with no rows anywhere are never visited: their output tiles are
    UNWRITTEN — the caller must mask them (nn/moe.py zeroes experts with
    zero tokens via the count vector).

    Args:
        x: (M, D); dy: (M, H); both sorted by group, M | block_rows.
        block_groups: (M // block_rows,) int32, non-decreasing.
        n_groups: E, the output's leading dim.
        with_rowsum: also return db (E, H) = per-group row sums of dy.
    Returns:
        dw (E, D, H) [, db (E, H)] in ``out_dtype`` (default x.dtype).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, d = x.shape
    m2, h = dy.shape
    if m2 != m:
        raise ValueError(f"x rows {m} != dy rows {m2}")
    if m % block_rows:
        raise ValueError(f"M={m} not a multiple of block_rows={block_rows}")
    out_dtype = out_dtype or x.dtype
    dp = _ceil_to(d, _LANE)
    block_h = min(block_h, _ceil_to(h, _LANE))
    br = block_rows
    block_rows, block_h = _fit_blocks(block_rows, block_h, dp,
                                      jnp.dtype(x.dtype).itemsize,
                                      scratch_rows=dp)
    if block_rows != br:
        block_groups = jnp.repeat(block_groups, br // block_rows)
    nb = m // block_rows
    hp = _ceil_to(h, block_h)
    xp = jnp.pad(x, ((0, 0), (0, dp - d)))
    dyp = jnp.pad(dy, ((0, 0), (0, hp - h)))
    scalars = block_groups.astype(jnp.int32)

    def kernel(scalar_ref, x_ref, dy_ref, dw_ref, db_ref, acc_scr, db_scr):
        i = pl.program_id(1)  # row-block index (inner)
        g = scalar_ref[i]
        prev = scalar_ref[jnp.maximum(i - 1, 0)]
        is_first = jnp.logical_or(i == 0, prev != g)

        @pl.when(is_first)
        def _():
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
            db_scr[...] = jnp.zeros(db_scr.shape, jnp.float32)

        acc_scr[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if with_rowsum:
            db_scr[...] += jnp.sum(dy_ref[...].astype(jnp.float32), axis=0,
                                   keepdims=True)

        nxt = scalar_ref[jnp.minimum(i + 1, nb - 1)]
        is_last = jnp.logical_or(i == nb - 1, nxt != g)

        @pl.when(is_last)
        def _():
            dw_ref[0] = acc_scr[...].astype(dw_ref.dtype)
            db_ref[0] = db_scr[...].astype(db_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(hp // block_h, nb),
        in_specs=[
            pl.BlockSpec((block_rows, dp), lambda j, i, s: (i, 0)),
            pl.BlockSpec((block_rows, block_h), lambda j, i, s: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, dp, block_h), lambda j, i, s: (s[i], 0, j)),
            pl.BlockSpec((1, 1, block_h), lambda j, i, s: (s[i], 0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((dp, block_h), jnp.float32),
            pltpu.VMEM((1, block_h), jnp.float32),
        ],
    )
    dw, db = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[_out_struct((n_groups, dp, hp), out_dtype, xp, dyp),
                   _out_struct((n_groups, 1, hp), out_dtype, xp, dyp)],
        interpret=_use_interpret(),
        name="tgmm",
    )(scalars, xp, dyp)
    dw = dw[:, :d, :h]
    return (dw, db[:, 0, :h]) if with_rowsum else dw


# ---------------------------------------------------------------------------
# differentiable wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def grouped_linear(x, w, bias, block_groups, n_live_blocks, group_present,
                   block_rows=512, block_h=512, name="gmm"):
    """Differentiable grouped linear: ``gmm(x, w, ...) + bias[group]`` with
    the three backward passes expressed as grouped matmuls over the same
    block map (dx via gmm against w^T, dw/db via tgmm) — no scatters.
    ``bias`` may be None (a gated expert has none).

    ``group_present`` (E,) bool marks groups with at least one routed row:
    tgmm never visits an absent group, leaving its dw/db tiles unwritten
    (garbage), so the backward zero-masks them here.  Rows must be sorted
    by group with block-aligned segments and ZERO padding rows — pad rows
    then contribute nothing to any of the three grads (their x and dy are
    both zero).  Integer/bool args take no gradient.  ``name`` names the
    forward kernel (see :func:`gmm`)."""
    return gmm(x, w, block_groups, n_live_blocks, bias=bias,
               block_rows=block_rows, block_h=block_h, name=name)


def _gl_fwd(x, w, bias, block_groups, n_live_blocks, group_present,
            block_rows, block_h, name):
    out = gmm(x, w, block_groups, n_live_blocks, bias=bias,
              block_rows=block_rows, block_h=block_h, name=name)
    return out, (x, w, bias, block_groups, n_live_blocks, group_present)


def _gl_bwd(block_rows, block_h, name, res, dy):
    x, w, bias, block_groups, n_live_blocks, group_present = res
    e, d, h = w.shape
    dx = gmm(dy, jnp.swapaxes(w, 1, 2), block_groups, n_live_blocks,
             block_rows=block_rows, block_h=block_h, out_dtype=x.dtype)
    db = None
    if d <= h:
        dw = tgmm(x, dy, block_groups, e, block_rows=block_rows,
                  block_h=block_h, with_rowsum=bias is not None,
                  out_dtype=w.dtype)
        if bias is not None:
            dw, db = dw
    else:
        # x wider than dy (e.g. the down-projection w2): tgmm's (D, bh)
        # f32 accumulator scales with the X side, so compute the
        # transposed product with the NARROW operand as x and swap —
        # measured necessary to keep 512-row tiles in VMEM at h=3072
        dw = jnp.swapaxes(
            tgmm(dy, x, block_groups, e, block_rows=block_rows,
                 block_h=block_h, out_dtype=w.dtype), 1, 2)
        if bias is not None:
            # bias grad = per-group row sums of dy: one elementwise pass
            # (block partial sums, then a tiny scatter-add over blocks;
            # dead tail blocks carry zero dy rows and contribute nothing)
            nb = dy.shape[0] // block_rows
            blk = dy.astype(jnp.float32).reshape(nb, block_rows, h).sum(1)
            db = (jnp.zeros((e, h), jnp.float32).at[block_groups].add(blk)
                  .astype(w.dtype))
    dw = jnp.where(group_present[:, None, None], dw, 0)
    if db is not None:
        db = jnp.where(group_present[:, None], db, 0)
    return dx, dw, db, None, None, None


grouped_linear.defvjp(_gl_fwd, _gl_bwd)

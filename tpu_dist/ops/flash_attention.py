"""Flash attention — Pallas TPU kernel with custom VJP.

The dense path in tpu_dist.nn.attention.scaled_dot_product_attention
materializes the (Tq, Tk) score matrix in HBM; fine for the reference's
image workloads, quadratic-memory death for long sequences.  This kernel is
the single-device half of the long-context story (the cross-device half is
tpu_dist.parallel.ring_attention, which rotates KV blocks over ICI with the
same online-softmax recurrence): Q/K/V tiles stream HBM -> VMEM, scores
live only in VMEM/registers, and the softmax is accumulated online (flash
recurrence), so memory is O(T) instead of O(T^2).

Two sizes of tile.  A GRID STEP holds one (block_q, block_k) tile of a
head: its Q, K and V blocks are what one DMA brings into VMEM, and the
1024 defaults are large so that few steps and few DMAs carry a sequence.
Inside a grid step the kernels walk the tile in SUB-TILES of
(_SUB x _SUB) scores, and a sub-tile is the unit that is computed, masked
or skipped:

- a sub-tile wholly above the causal diagonal, or wholly inside K's
  padding, is never computed: no MXU pass, no exp, no mask;
- a sub-tile wholly below the diagonal and inside the real keys takes no
  mask math (no iotas, compare, select);
- only a sub-tile that the diagonal or the padding's edge crosses masks.

So causal attention at T = 1024 is one grid step a head and executes 10
of its 16 sub-tiles of 256 (``tile_plan``).  Every kernel walks a tile
the same way: by ROW of sub-tiles (``_SUB`` queries), each row against the
keys ``_k_range`` leaves it — its unmasked sub-tiles first, as one piece
of scores, then those that mask — and on TRANSPOSED scores (keys x
queries): a row's q (and dO) stay in the MXU while its keys stream past,
every per-query statistic (running max and sum, lse, delta) is a row
(1, _SUB) that broadcasts down the keys, and the softmax's reductions run
down the keys too, vreg on vreg, not across lanes.  A pass first makes
every row's score matmuls, then every row's elementwise work, then every
row's output matmuls: the order the chip's scheduler overlaps best.

Which sub-tiles run is plain integer arithmetic on a tile's position.
When a kernel is traced it lists the few ways a live tile of its grid can
lie against the diagonal and K's end (``_by_kind``: the diagonal's tile, a
tile below it, the one K's padding ends in) and unrolls one body a kind
with static slices; a grid step computes its own ranges as scalars and
runs the body they equal.

Layout (kernel-internal): (BH, T, D) with a (BH, nq, nk) grid; the KV index
is innermost so the f32 accumulators (m, l, acc) persist in VMEM scratch
across a Q row's KV sweep and the output tile is written back to HBM once.
Forward saves per-row logsumexp; backward recomputes scores from
(q, k, lse) flash-style, in ONE kernel (``flash_bwd_dq_dkv``): a
sub-tile's scores and probabilities are made once, and dV, dK and dQ are
three matmuls from the same p^T and ds^T (5 matmuls a sub-tile).  Its grid
is transposed, (BH, nk, nq), so dK and dV accumulate in VMEM over the Q
sweep; dQ accumulates in a float32 scratch of the head's WHOLE padded
sequence, under an output block whose index does not change while the
head's tiles are swept, and is written back once a head.  Whether that
fits is read off the shapes (``_bwd_vmem``, ``backward_plan``): a very long
sequence at a wide head takes two kernels instead, one accumulating dQ
over the KV sweep, one accumulating dK/dV over the Q sweep, each making the
scores and probabilities for itself (7 matmuls a sub-tile).  Residuals are
just (q, k, v, o, lse): no (Tq, Tk) tensor is ever materialized, forward or
backward.

The FORWARD kernel takes a value width of its own: q and k share one head
size D, v may have another (a latent layer's expanded heads are 192 for q
and k, 128 for v), and only V's block, the accumulator and the output are
Dv wide; the sub-tiles, the statistics and ``tile_plan`` do not see it, and
with Dv == D the call lowers to the program it always was.  The
backward kernels take ONE head size: differentiating a call with Dv != D
raises ``NotImplementedError``.  Its caller is a latent layer's
whole-prompt prefill (tpu_dist.nn.mla, through
:func:`flash_attention_heads_first`, which takes and returns the kernel's
own (..., H, T, D) order).

Grid tiles entirely above the diagonal match no kind and run nothing (the
grid still sweeps them).  Runs on TPU via Mosaic; everywhere else (CPU
tests) through ``interpret=True`` — same kernel, same numerics (tests
compare forward and grads against the dense composition).

The reference has no attention at all (SURVEY.md §5 long-context row:
absent — its workloads are 28^2/32^2 image classifiers); this kernel plus
ring attention is the beyond-parity long-context substrate.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ._pallas import (ceil_to as _ceil_to, out_struct as _out_struct,
                      use_interpret as _use_interpret)

__all__ = ["flash_attention", "flash_attention_with_lse",
           "flash_attention_heads_first", "tile_plan", "backward_plan"]

_LANE = 128
_D_ALIGN = 64  # head_dim alignment: 64 halves K/V DMA for d=64 vs padding to 128
_NEG_INF = -1e30  # finite: keeps max/correction arithmetic NaN-free when a
                  # whole tile is masked (same sentinel as ring_attention)
_SUB = 256  # edge of a sub-tile; timed on the chip against 128 and 512
            # (PERF.md section 6, PR 33)
_VMEM_BUDGET = 12 * 1024 * 1024  # of the 16 MB a kernel may take; ops/gmm.py's


def _clamp_blocks(dtype, tq, tk, block_q, block_k):
    """Grid-step tile sizes that fit VMEM: the 1024 defaults are for bf16;
    for 4-byte inputs they are halved (the dK/dV kernel passed the ~16 MB
    VMEM budget at 1024^2 when it held whole-tile f32 intermediates; not
    timed again since the intermediates are sub-tiles)."""
    if jnp.dtype(dtype).itemsize >= 4:
        block_q = min(block_q, 512)
        block_k = min(block_k, 512)
    return (min(block_q, _ceil_to(tq, _LANE)),
            min(block_k, _ceil_to(tk, _LANE)))


def _bwd_vmem(dtype, tq, tk, d, block_q, block_k):
    """``(bytes, dq bytes)``: an estimate of the VMEM the ONE backward
    kernel takes at these shapes, and the part of it that holds a head's
    whole dQ (its float32 accumulator and its double-buffered output
    block).  Counted the way ops/gmm.py's ``_fit_blocks`` counts: the
    blocks double-buffered, the accumulators, and the intermediates of a
    tile, for which Mosaic was seen to want half of a whole tile's float32
    scores and ``dp`` and the ``p`` and ``ds`` cast from them (compiled for a
    v5e under a falling limit, the estimate is within 0.3 MB of the least
    the compiler takes where every sub-tile is live and above it elsewhere:
    PERF.md section 6, PR 50)."""
    item = jnp.dtype(dtype).itemsize
    block_q, block_k = _clamp_blocks(dtype, tq, tk, block_q, block_k)
    tqp, dp = _ceil_to(tq, block_q), _ceil_to(d, _D_ALIGN)
    dq = tqp * dp * (4 + 2 * item)
    blocks = 2 * (2 * block_q + 4 * block_k) * dp * item  # q dO k v, dk dv
    acc = 2 * block_k * dp * 4
    tile = block_q * block_k * (4 + item)
    return dq + blocks + acc + tile, dq


def _one_kernel_fits(dtype, tq, tk, d, block_q, block_k):
    """Does a head's whole dQ fit VMEM beside what the dK/dV kernel holds:
    is the backward pass one kernel (_make_bwd_kernel) or two."""
    return _bwd_vmem(dtype, tq, tk, d, block_q, block_k)[0] <= _VMEM_BUDGET


def _sub_edge(block):
    """Edge of the sub-tiles a grid step of ``block`` rows (or columns) is
    walked in: the largest divisor of the block that _SUB allows, or the
    whole block where that would leave the 128-lane tiling."""
    sub = math.gcd(block, _SUB)
    return sub if sub % _LANE == 0 else block


# ---------------------------------------------------------------------------
# which sub-tiles run: integer arithmetic on Python ints or kernel scalars
# ---------------------------------------------------------------------------

def _k_range(causal, q_lo, sq, k_lo, sk, n_sk, tk):
    """``(n_full, n_live)`` for the q sub-tile of rows [q_lo, q_lo + sq)
    against the ``n_sk`` k sub-tiles of ``sk`` columns that start at
    ``k_lo``: sub-tiles [0, n_full) hold visible scores only and take no
    mask, [n_full, n_live) are crossed by the diagonal or by the end of the
    real keys and mask, [n_live, n_sk) hold nothing visible and are never
    computed.  ``causal`` as in _visible; positions are Python ints
    (``tile_plan``, ``_by_kind``) or scalars of a kernel."""
    ints = isinstance(q_lo, int) and isinstance(k_lo, int)
    lo, clip = (min, lambda x: min(max(x, 0), n_sk * sk)) if ints else (
        jnp.minimum, lambda x: jnp.clip(x, 0, n_sk * sk))
    full = live = tk - k_lo              # columns before K's padding
    if causal is True:
        full = lo(full, q_lo + 1 - k_lo)     # ... that all rows see
        live = lo(live, q_lo + sq - k_lo)    # ... that the last row sees
    return clip(full) // sk, (clip(live) + sk - 1) // sk


def _tile_live(causal, q_lo, k_lo, block_q, block_k):
    """Grid predicate: does tile (q_lo, k_lo) contribute any unmasked
    entries?  ``True`` = tiles intersecting or below the diagonal;
    ``"offdiag"`` = tiles STRICTLY below the diagonal band; ``False`` = all
    tiles."""
    if causal is True:
        return k_lo <= q_lo + block_q - 1
    if causal == "offdiag":
        return k_lo + block_k <= q_lo
    return k_lo >= 0  # trivially true (kernel body must sit under pl.when)


def _plan(causal, tk, block_q, block_k):
    """``(sq, sk, k_ranges)``: the sub-tile's edges in a grid tile of these
    blocks, and the tile at ``(q_lo, k_lo)``'s _k_range a q sub-tile."""
    sq, sk = _sub_edge(block_q), _sub_edge(block_k)

    def k_ranges(q_lo, k_lo):
        return tuple(_k_range(causal, q_lo + r, sq, k_lo, sk, block_k // sk,
                              tk) for r in range(0, block_q, sq))
    return sq, sk, k_ranges


def _live_tiles(causal, nq, nk, block_q, block_k):
    return [(q_lo, k_lo) for q_lo in range(0, nq * block_q, block_q)
            for k_lo in range(0, nk * block_k, block_k)
            if _tile_live(causal, q_lo, k_lo, block_q, block_k)]


def tile_plan(tq, tk, causal, block_q: int = 1024, block_k: int = 1024,
              dtype=jnp.bfloat16) -> dict:
    """What one head's attention call executes, from shapes alone, by the
    arithmetic the kernels run on (_tile_live, _k_range).

    ``executed``: sub-tiles a pass computes; ``masked``: those of them that
    take mask math; ``total``: sub-tiles of the padded (Tq, Tk) grid;
    ``needed``: the visible (query, key) pairs in units of one sub-tile's
    area, what the mathematics asks for; ``sub_q`` / ``sub_k``: a sub-tile's
    edges.  ``executed / needed`` is 1.0 when nothing masked or padded is
    computed: 10 sub-tiles of 256 for 8.008 needed, 1.249, at causal
    T = 1024."""
    if not isinstance(causal, str):
        causal = bool(causal)
    block_q, block_k = _clamp_blocks(dtype, tq, tk, block_q, block_k)
    sq, sk, k_ranges = _plan(causal, tk, block_q, block_k)
    nq, nk = -(-tq // block_q), -(-tk // block_k)
    ranges = [r for tile in _live_tiles(causal, nq, nk, block_q, block_k)
              for r in k_ranges(*tile)]
    if causal is True:
        pairs = sum(min(q + 1, tk) for q in range(tq))
    elif causal == "offdiag":
        pairs = sum(min(q // block_q * block_q // block_k * block_k, tk)
                    for q in range(tq))
    else:
        pairs = tq * tk
    return {"executed": sum(live for _, live in ranges),
            "masked": sum(live - full for full, live in ranges),
            "total": nq * (block_q // sq) * nk * (block_k // sk),
            "needed": pairs / (sq * sk), "sub_q": sq, "sub_k": sk}


def backward_plan(tq, tk, d, causal, block_q: int = 1024,
                  block_k: int = 1024, dtype=jnp.bfloat16) -> dict:
    """How one head's backward pass is made, from shapes alone, by the
    function ``_bwd_call`` decides with.

    ``kernels``: 1 where a head's whole dQ fits VMEM beside the dK/dV
    kernel's blocks (``flash_bwd_dq_dkv``: the scores and probabilities of
    a sub-tile made once for dQ, dK and dV), else 2 (``flash_bwd_dq`` and
    ``flash_bwd_dkv``, each making them); ``score_passes``: sub-tiles of
    scores the backward pass computes, ``kernels`` times
    :func:`tile_plan`'s ``executed``; ``dq_resident_bytes``: the VMEM a
    head's dQ takes in the one kernel (its float32 accumulator and its
    double-buffered output block), whichever is chosen."""
    _, dq = _bwd_vmem(dtype, tq, tk, d, block_q, block_k)
    kernels = 1 if _one_kernel_fits(dtype, tq, tk, d, block_q, block_k) else 2
    executed = tile_plan(tq, tk, causal, block_q, block_k, dtype)["executed"]
    return {"kernels": kernels, "score_passes": kernels * executed,
            "dq_resident_bytes": dq}


# ---------------------------------------------------------------------------
# what the kernels share
# ---------------------------------------------------------------------------

def _scores_t(k, q, sm_scale):
    """Scaled scores of a q sub-tile against a run of keys, TRANSPOSED:
    (keys, queries), on the MXU with f32 accumulation."""
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s * sm_scale


def _visible(shape, causal, tk, q_lo, k_lo):
    """Mask of a (keys, queries) piece of scores whose first query is
    ``q_lo`` and first key ``k_lo`` (scalars of the kernel).  The single
    source of the mask convention shared by the forward and the backward
    kernels.

    ``causal`` is three-valued: ``True`` masks above the diagonal,
    ``False`` doesn't, and ``"offdiag"`` also doesn't — its tiles sit
    strictly below the diagonal band by the grid predicate; only the K
    padding range check remains."""
    key = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    mask = key < tk - k_lo
    if causal is True:
        query = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = mask & (key - query <= q_lo - k_lo)
    return mask


def _sub_tiles(kind, sq, sk):
    """``(rows, first query, full, live)`` of each q sub-tile of a kind
    that executes anything: its queries as a slice of the block, and the
    keys it runs as counts from the block's first — [0, full) unmasked,
    [full, live) masked."""
    return [(slice(i * sq, (i + 1) * sq), i * sq, n_full * sk, n_live * sk)
            for i, (n_full, n_live) in enumerate(kind) if n_live]


def _key_pieces(x, full, live):
    """A sub-tile's (keys, ...) array as ``(piece, first key, masked)``: the
    run of sub-tiles that take no mask, then the run that does."""
    return [(x[a:b], a, masked)
            for a, b, masked in ((0, full, False), (full, live, True))
            if b > a]


def _join(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _bwd_pieces(refs, tiles, sm_scale, causal, tk, q_lo, k_lo):
    """``(p^T, ds^T)`` in the inputs' dtype, (keys, queries), of each q
    sub-tile in ``tiles``: the part of the backward pass that dQ and dK/dV
    both need.  ds = p * (dp - delta); q/k/v/do stay in their input dtype:
    bf16 inputs run bf16 MXU passes with f32 accumulation."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs
    made = [(_scores_t(k_ref[0, :live, :], q_ref[0, rows, :], sm_scale),
             jax.lax.dot_general(v_ref[0, :live, :], do_ref[0, rows, :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32))
            for rows, _, _, live in tiles]
    out = []
    for (rows, r0, full, live), (s, dp) in zip(tiles, made):
        ps, dss = [], []
        for x, c0, masked in _key_pieces(s, full, live):
            # the flash-backward recurrence: probabilities recomputed from
            # the scores and the queries' lse
            p = jnp.exp(x - lse_ref[0, :, rows])
            if masked:
                p = jnp.where(_visible(p.shape, causal, tk, q_lo + r0,
                                       k_lo + c0), p, 0.0)
            ps.append(p.astype(q_ref.dtype))
            dss.append((p * (dp[c0:c0 + x.shape[0]] - delta_ref[0, :, rows])
                        ).astype(q_ref.dtype))
        out.append((_join(ps), _join(dss)))
    return out


def _by_kind(causal, tk, block_q, block_k, nq, nk):
    """``(sq, sk, run)`` for a kernel over this grid.  When the kernel is
    traced, the distinct ``k_ranges`` of the grid's live tiles are listed:
    every KIND of tile, every way one can lie against the diagonal and K's
    end.  ``run(q_lo, k_lo, body)``, in a grid step whose tile starts at
    those scalars, runs ``body(kind)`` for the kind the step's own ranges
    equal, and nothing for a tile that is not live: each body's slices are
    static, its sweep unrolled, and it sits under ``pl.when`` (see
    _use_interpret for why it must either way)."""
    from jax.experimental import pallas as pl

    sq, sk, k_ranges = _plan(causal, tk, block_q, block_k)
    kinds = list(dict.fromkeys(
        k_ranges(*tile)
        for tile in _live_tiles(causal, nq, nk, block_q, block_k)))

    def run(q_lo, k_lo, body):
        ranges = k_ranges(q_lo, k_lo)
        live = _tile_live(causal, q_lo, k_lo, block_q, block_k)
        for kind in kinds:
            hit = live
            for (a, b), (ka, kb) in zip(ranges, kind):
                hit = hit & (a == ka) & (b == kb)
            pl.when(hit)(functools.partial(body, kind))
    return sq, sk, run


def _col(x):
    """A row (1, n) of per-query statistics as a column (n, 1)."""
    return jnp.transpose(jnp.broadcast_to(x, (_LANE, x.shape[1])))[:, 0:1]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _make_fwd_kernel(sm_scale, tk, block_q, block_k, causal, nq, nk):
    from jax.experimental import pallas as pl

    sq, sk, run = _by_kind(causal, tk, block_q, block_k, nq, nk)

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
        ki = pl.program_id(2)
        q_lo = pl.program_id(1) * block_q
        k_lo = ki * block_k

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

        def body(kind):
            tiles = _sub_tiles(kind, sq, sk)
            scores = [_scores_t(k_ref[0, :live, :], q_ref[0, rows, :],
                                sm_scale) for rows, _, _, live in tiles]
            probs = []
            for (rows, r0, full, live), s in zip(tiles, scores):
                # one online-softmax step a q sub-tile, statistics as rows
                pieces = []
                for x, c0, masked in _key_pieces(s, full, live):
                    mask = None
                    if masked:
                        mask = _visible(x.shape, causal, tk, q_lo + r0,
                                        k_lo + c0)
                        x = jnp.where(mask, x, _NEG_INF)
                    pieces.append((x, mask))
                m_prev = m_scr[:, rows]
                m_new = m_prev
                for x, _ in pieces:
                    m_new = jnp.maximum(m_new,
                                        jnp.max(x, axis=0, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                l_new = alpha * l_scr[:, rows]
                ps = []
                for x, mask in pieces:
                    p = jnp.exp(x - m_new)
                    if mask is not None:
                        # fully-masked queries: x == m_new == _NEG_INF gives
                        # exp(0) = 1; zero them so they contribute nothing
                        p = jnp.where(mask, p, 0.0)
                    l_new = l_new + jnp.sum(p, axis=0, keepdims=True)
                    ps.append(p.astype(v_ref.dtype))
                m_scr[:, rows] = m_new
                l_scr[:, rows] = l_new
                probs.append((_join(ps), alpha))
            for (rows, _, _, live), (p, alpha) in zip(tiles, probs):
                pv = jax.lax.dot_general(p, v_ref[0, :live, :],
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                if nk > 1:      # an earlier k tile's part, at the new max
                    pv = pv + acc_scr[rows, :] * _col(alpha)
                acc_scr[rows, :] = pv

        run(q_lo, k_lo, body)

        @pl.when(ki == nk - 1)
        def _fin():
            l = l_scr[:]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_scr[:] / _col(l_safe)).astype(o_ref.dtype)
            lse_ref[0] = m_scr[:] + jnp.log(l_safe)

    return kernel


# jitted so that a model's layers share ONE trace and one lowered function
# of the call: the kernels' bodies are unrolled, and tracing and lowering them
# afresh at each of 24 layers' call sites cost a training process 50 s of
# set-up on every start
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _fwd_call(q, k, v, causal, sm_scale, block_q, block_k):
    """q: (BH, Tq, D); k: (BH, Tk, D); v: (BH, Tk, Dv) -> (o, lse) with o
    (BH, Tq, Dv) and lse (BH, Tq, 1).  The value width is v's own: only V's
    block, the accumulator and the output are as wide as Dv, and where
    Dv == D the call lowers to the program it always was."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk, dv = k.shape[1], v.shape[2]
    block_q, block_k = _clamp_blocks(q.dtype, tq, tk, block_q, block_k)
    tqp, tkp, dp = _ceil_to(tq, block_q), _ceil_to(tk, block_k), _ceil_to(d, _D_ALIGN)
    dvp = _ceil_to(dv, _D_ALIGN)
    qp = jnp.pad(q, ((0, 0), (0, tqp - tq), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, tkp - tk), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, tkp - tk), (0, dvp - dv)))
    nq, nk = tqp // block_q, tkp // block_k
    o, lse = pl.pallas_call(
        _make_fwd_kernel(sm_scale, tk, block_q, block_k, causal, nq, nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dvp), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dvp), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct((bh, tqp, dvp), q.dtype, qp, kp, vp),
            _out_struct((bh, 1, tqp), jnp.float32, qp, kp, vp),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),       # running max m
            pltpu.VMEM((1, block_q), jnp.float32),       # running sum l
            pltpu.VMEM((block_q, dvp), jnp.float32),     # output accumulator
        ],
        interpret=_use_interpret(),
        name="flash_fwd",
    )(qp, kp, vp)
    return o[:, :tq, :dv], lse[:, 0, :tq, None]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _make_dq_kernel(sm_scale, tk, block_q, block_k, causal, nq, nk):
    from jax.experimental import pallas as pl

    sq, sk, run = _by_kind(causal, tk, block_q, block_k, nq, nk)

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr):
        ki = pl.program_id(2)
        q_lo = pl.program_id(1) * block_q
        k_lo = ki * block_k

        @pl.when(ki == 0)
        def _init():
            acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

        def body(kind):
            tiles = _sub_tiles(kind, sq, sk)
            pieces = _bwd_pieces(
                (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), tiles,
                sm_scale, causal, tk, q_lo, k_lo)
            for (rows, _, _, live), (_, ds) in zip(tiles, pieces):
                acc_scr[rows, :] = acc_scr[rows, :] + (
                    sm_scale * jax.lax.dot_general(
                        ds, k_ref[0, :live, :], (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))

        run(q_lo, k_lo, body)

        @pl.when(ki == nk - 1)
        def _fin():
            dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)

    return kernel


def _add_dkv(dk_scr, dv_scr, q_ref, do_ref, rows, live, p, ds, sm_scale):
    """A row of sub-tiles' part of dV and dK, from its p^T and ds^T, into the
    accumulators of the ``live`` keys it ran.  Padded q rows contribute
    nothing: their do and delta are zero."""
    dv_scr[:live, :] = dv_scr[:live, :] + jax.lax.dot_general(
        p, do_ref[0, rows, :], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dk_scr[:live, :] = dk_scr[:live, :] + sm_scale * jax.lax.dot_general(
        ds, q_ref[0, rows, :], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _make_dkv_kernel(sm_scale, tk, block_q, block_k, causal, nq, nk):
    from jax.experimental import pallas as pl

    sq, sk, run = _by_kind(causal, tk, block_q, block_k, nq, nk)

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dk_ref, dv_ref, dk_scr, dv_scr):
        qi = pl.program_id(2)
        k_lo = pl.program_id(1) * block_k
        q_lo = qi * block_q

        @pl.when(qi == 0)
        def _init():
            dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
            dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

        def body(kind):
            tiles = _sub_tiles(kind, sq, sk)
            pieces = _bwd_pieces(
                (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), tiles,
                sm_scale, causal, tk, q_lo, k_lo)
            for (rows, _, _, live), (p, ds) in zip(tiles, pieces):
                _add_dkv(dk_scr, dv_scr, q_ref, do_ref, rows, live, p, ds,
                         sm_scale)

        run(q_lo, k_lo, body)

        @pl.when(qi == nq - 1)
        def _fin():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    return kernel


def _make_bwd_kernel(sm_scale, tk, block_q, block_k, causal, nq, nk):
    """dQ, dK and dV of a head in one kernel, on the dK/dV kernel's grid
    (KV tile outer, Q sweep inner): ``_bwd_pieces`` once a tile, and all
    three output matmuls from its p^T and ds^T.  dk and dv accumulate over
    the Q sweep as in _make_dkv_kernel; dq accumulates in a float32 scratch
    of the head's WHOLE padded sequence, and its output block does not move
    while the head's tiles are swept, so it is written back once a head."""
    from jax.experimental import pallas as pl

    sq, sk, run = _by_kind(causal, tk, block_q, block_k, nq, nk)

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr):
        ki, qi = pl.program_id(1), pl.program_id(2)
        k_lo = ki * block_k
        q_lo = qi * block_q

        def of_head(rows):
            """A tile's rows in the head's dq accumulator."""
            if nq == 1:
                return rows
            return pl.ds(pl.multiple_of(q_lo + rows.start, sq), sq)

        @pl.when((ki == 0) & (qi == 0))
        def _init_dq():
            dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

        @pl.when(qi == 0)
        def _init():
            dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
            dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

        def body(kind):
            tiles = _sub_tiles(kind, sq, sk)
            pieces = _bwd_pieces(
                (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), tiles,
                sm_scale, causal, tk, q_lo, k_lo)
            for (rows, _, _, live), (p, ds) in zip(tiles, pieces):
                _add_dkv(dk_scr, dv_scr, q_ref, do_ref, rows, live, p, ds,
                         sm_scale)
                at = of_head(rows)
                dq_scr[at, :] = dq_scr[at, :] + sm_scale * jax.lax.dot_general(
                    ds, k_ref[0, :live, :], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        run(q_lo, k_lo, body)

        @pl.when(qi == nq - 1)
        def _fin():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

        @pl.when((ki == nk - 1) & (qi == nq - 1))
        def _fin_dq():
            dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    return kernel


def _specs_kv_outer(block_q, block_k, dp):
    """``(q, kv, row)`` block specs of a grid (bh, nk, nq): KV tile outer,
    Q sweep inner."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return (pl.BlockSpec((1, block_q, dp), lambda b, j, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dp), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i),
                         memory_space=pltpu.VMEM))


def _bwd_two_kernels(operands, tk, block_q, block_k, causal, sm_scale):
    """The backward pass as two kernels, each making the scores and
    probabilities for itself: dQ accumulated over the KV sweep, dK/dV over
    the Q sweep on the grid transposed.  What a call takes whose dQ does
    not fit VMEM whole (``backward_plan``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qp, kp, vp = operands[:3]
    (bh, tqp, dp), tkp = qp.shape, kp.shape[1]
    nq, nk = tqp // block_q, tkp // block_k
    q_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec_dq = pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0),
                              memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                            memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        _make_dq_kernel(sm_scale, tk, block_q, block_k, causal, nq, nk),
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec_dq, kv_spec_dq, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=_out_struct((bh, tqp, dp), qp.dtype, *operands[:4]),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        interpret=_use_interpret(),
        name="flash_bwd_dq",
    )(*operands)

    # grid transposed: KV tile outer, Q sweep inner, so dk/dv accumulate
    q_spec_t, kv_spec_t, row_spec_t = _specs_kv_outer(block_q, block_k, dp)
    dk, dv = pl.pallas_call(
        _make_dkv_kernel(sm_scale, tk, block_q, block_k, causal, nq, nk),
        grid=(bh, nk, nq),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[_out_struct((bh, tkp, dp), kp.dtype, *operands[:4]),
                   _out_struct((bh, tkp, dp), vp.dtype, *operands[:4])],
        scratch_shapes=[pltpu.VMEM((block_k, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32)],
        interpret=_use_interpret(),
        name="flash_bwd_dkv",
    )(*operands)
    return dq, dk, dv


def _bwd_one_kernel(operands, tk, block_q, block_k, causal, sm_scale):
    """The backward pass as ONE kernel (_make_bwd_kernel).  Its name holds
    ``flash_bwd_dq``, which is what a device trace's readers look for, and
    does not hold ``flash_bwd_dkv``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qp, kp, vp = operands[:3]
    (bh, tqp, dp), tkp = qp.shape, kp.shape[1]
    nq, nk = tqp // block_q, tkp // block_k
    q_spec, kv_spec, row_spec = _specs_kv_outer(block_q, block_k, dp)
    # the head's whole dq: the block's index does not change over a head's
    # tiles, so it stays in VMEM and is written back once
    dq_spec = pl.BlockSpec((1, tqp, dp), lambda b, j, i: (b, 0, 0),
                           memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _make_bwd_kernel(sm_scale, tk, block_q, block_k, causal, nq, nk),
        grid=(bh, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[dq_spec, kv_spec, kv_spec],
        out_shape=[_out_struct((bh, tqp, dp), qp.dtype, *operands[:4]),
                   _out_struct((bh, tkp, dp), kp.dtype, *operands[:4]),
                   _out_struct((bh, tkp, dp), vp.dtype, *operands[:4])],
        scratch_shapes=[pltpu.VMEM((tqp, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32)],
        interpret=_use_interpret(),
        name="flash_bwd_dq_dkv",
    )(*operands)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _bwd_call(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k,
              dlse=None):
    bh, tq, d = q.shape
    tk = k.shape[1]
    one = _one_kernel_fits(q.dtype, tq, tk, d, block_q, block_k)
    block_q, block_k = _clamp_blocks(q.dtype, tq, tk, block_q, block_k)
    tqp, tkp, dp = _ceil_to(tq, block_q), _ceil_to(tk, block_k), _ceil_to(d, _D_ALIGN)

    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian correction term;
    # cheap elementwise jnp, fused by XLA around the kernels.  When the
    # caller differentiates through lse too (ring-attention merge), its
    # cotangent enters the same place with opposite sign:
    # dL/ds_ij = p_ij * (dp_ij - delta_i + dlse_i), so fold it into delta.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)              # (BH, Tq, 1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    qp = jnp.pad(q, ((0, 0), (0, tqp - tq), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, tkp - tk), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, tkp - tk), (0, dp - d)))
    dop = jnp.pad(do, ((0, 0), (0, tqp - tq), (0, dp - d)))
    # per-query statistics as rows (BH, 1, Tq): the same bytes
    pad_row = ((0, 0), (0, 0), (0, tqp - tq))
    lsep = jnp.pad(lse.reshape(bh, 1, tq), pad_row)
    deltap = jnp.pad(delta.reshape(bh, 1, tq), pad_row)

    make = _bwd_one_kernel if one else _bwd_two_kernels
    dq, dk, dv = make((qp, kp, vp, dop, lsep, deltap), tk, block_q, block_k,
                      causal, sm_scale)
    return dq[:, :tq, :d], dk[:, :tk, :d], dv[:, :tk, :d]


# ---------------------------------------------------------------------------
# custom VJP + public wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, sm_scale, block_q, block_k):
    """Like _flash but also returns the per-row logsumexp — the merge
    currency of blockwise/ring attention.  Differentiable in BOTH outputs."""
    return _fwd_call(q, k, v, causal, sm_scale, block_q, block_k)


def _flash_lse_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            f"flash attention with a value width of its own (q/k heads of "
            f"{q.shape[-1]}, v heads of {v.shape[-1]}) has a forward kernel "
            f"only: the backward kernels take one head size.  "
            f"Differentiate the dense composition (impl='dense'), or pad v "
            f"to the q/k width")
    o, lse = _fwd_call(q, k, v, causal, sm_scale, block_q, block_k)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, sm_scale, block_q, block_k, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _bwd_call(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k,
                     dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _checked(q, k, v, causal, sm_scale, t_axis):
    """The wrappers' shared checks: ``(h, tq, tk, d, dv, causal, sm_scale)``
    of operands whose time axis is ``t_axis`` (-3: ``(..., T, H, D)``; -2:
    ``(..., H, T, D)``)."""
    if q.ndim < 3:
        order = "(..., T, H, D)" if t_axis == -3 else "(..., H, T, D)"
        raise ValueError(f"expected {order}, got {q.shape}")
    h_axis = -5 - t_axis
    h, tq, d = q.shape[h_axis], q.shape[t_axis], q.shape[-1]
    tk, dv = k.shape[t_axis], v.shape[-1]
    if not (q.shape[:-3] == k.shape[:-3] == v.shape[:-3]
            and k.shape[h_axis] == v.shape[h_axis] == h
            and k.shape[-1] == d and v.shape[t_axis] == tk):
        # no numpy-broadcast batch semantics here: the (B*H, T, D) flatten
        # would silently misalign batches — use impl='dense' for shared KV
        raise ValueError(
            f"flash_attention needs identical batch/head dims for q, k, v "
            f"(v's head size alone may differ); "
            f"got q={q.shape}, k={k.shape}, v={v.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if not isinstance(causal, str):
        # normalize truthy values (np.bool_, 1) to the literal bool the
        # kernels' three-valued dispatch (`causal is True`) relies on
        causal = bool(causal)
    return h, tq, tk, d, dv, causal, float(sm_scale)


def flash_attention_with_lse(q, k, v, causal: bool = False, sm_scale=None,
                             block_q: int = 1024, block_k: int = 1024):
    """Flash attention returning ``(out, lse)``.

    ``out``: (..., Tq, H, Dv) like :func:`flash_attention`; ``lse``:
    (..., Tq, H) float32 per-row logsumexp of the scaled scores.  Partial
    results ``(out_a, lse_a), (out_b, lse_b)`` over disjoint KV blocks merge
    exactly (the blockwise-attention identity used by
    tpu_dist.parallel.ring_attention)::

        m = max(lse_a, lse_b); w = exp(lse_? - m)
        out = (out_a*w_a + out_b*w_b) / (w_a + w_b); lse = m + log(w_a + w_b)

    Differentiable in both outputs (the lse cotangent folds into the
    softmax-jacobian correction).  Rows with no visible keys get lse ≈ -1e30
    and out 0 — the merge weight exp(lse - m) then vanishes exactly.

    ``causal``: ``True`` masks above the diagonal and executes only the
    sub-tiles at or below it; ``False`` and ``"offdiag"`` (every key of the
    grid tiles strictly below the diagonal band of ``block_q`` rows, none
    of the others) execute every sub-tile that holds a real key, and mask
    only where K's padding begins.  Grid steps and sub-tiles: the module's
    docstring and :func:`tile_plan`.

    ``v``'s head size may differ from ``q``'s and ``k``'s (a latent layer's
    192 / 128): the FORWARD kernel takes the value width from ``v``; the
    backward kernels do not, and differentiating such a call raises
    ``NotImplementedError``.
    """
    h, tq, tk, d, dv, causal, sm_scale = _checked(q, k, v, causal, sm_scale,
                                                  -3)
    lead = q.shape[:-3]

    def to3(x, t, width):
        x = x.reshape(-1, t, h, width)
        return jnp.swapaxes(x, 1, 2).reshape(-1, t, width)

    o3, lse3 = _flash_lse(to3(q, tq, d), to3(k, tk, d), to3(v, tk, dv),
                          causal, sm_scale, int(block_q), int(block_k))
    o = jnp.swapaxes(o3.reshape(-1, h, tq, dv), 1, 2).reshape(*lead, tq, h, dv)
    lse = jnp.swapaxes(lse3.reshape(-1, h, tq), 1, 2)       # (B, Tq, H)
    return o, lse.reshape(*lead, tq, h)


def flash_attention_heads_first(q, k, v, causal: bool = False, sm_scale=None,
                                block_q: int = 1024, block_k: int = 1024):
    """:func:`flash_attention` over operands in the kernel's OWN order:
    ``q`` (..., H, Tq, D), ``k`` (..., H, Tk, D), ``v`` (..., H, Tk, Dv) ->
    (..., H, Tq, Dv), with no transpose on either side of the call (the
    reshapes to ``(B H, T, D)`` move nothing).  For a caller that can
    produce its heads first and consume them so, as a latent layer's
    prefill does (tpu_dist.nn.mla): it rebuilds keys and values by an
    einsum whose result order is its own choice, and its output projection
    contracts ``(h, d)`` wherever they lie."""
    h, tq, tk, d, dv, causal, sm_scale = _checked(q, k, v, causal, sm_scale,
                                                  -2)
    o3, _ = _flash_lse(q.reshape(-1, tq, d), k.reshape(-1, tk, d),
                       v.reshape(-1, tk, dv), causal, sm_scale,
                       int(block_q), int(block_k))
    return o3.reshape(*q.shape[:-1], dv)


def flash_attention(q, k, v, causal: bool = False, sm_scale=None,
                    block_q: int = 1024, block_k: int = 1024):
    """Flash attention.  ``q``: (..., Tq, H, D); ``k``: (..., Tk, H, D);
    ``v``: (..., Tk, H, Dv), Dv = D but for a forward-only call
    (:func:`flash_attention_with_lse`).

    Drop-in for :func:`tpu_dist.nn.attention.scaled_dot_product_attention`
    (mask=None); differentiable; O(T) memory.  ``block_q``/``block_k`` are
    the sizes of a GRID STEP's tile, what one DMA brings into VMEM
    (auto-clamped for short sequences, halved for 4-byte inputs).  They
    stay at 1024 because a grid step and its DMAs cost the same whatever
    the tile holds: a sequence of 1024 is one step a head.  What is
    computed is decided a level below, by SUB-TILE (``_SUB`` squared scores,
    a constant timed on the chip): a causal call never computes a sub-tile
    above the diagonal, takes no mask math in one wholly below it, and
    masks only those the diagonal crosses, forward and backward;
    :func:`tile_plan` counts them from shapes, :func:`backward_plan` how
    many times the backward pass computes them.

    Same computation as :func:`flash_attention_with_lse` with the lse
    discarded (its cotangent is then zero, so the backward is identical).
    """
    return flash_attention_with_lse(q, k, v, causal=causal,
                                    sm_scale=sm_scale, block_q=block_q,
                                    block_k=block_k)[0]

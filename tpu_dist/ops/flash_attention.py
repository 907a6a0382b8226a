"""Flash attention — Pallas TPU kernel with custom VJP.

The dense path in tpu_dist.nn.attention.scaled_dot_product_attention
materializes the (Tq, Tk) score matrix in HBM; fine for the reference's
image workloads, quadratic-memory death for long sequences.  This kernel is
the single-device half of the long-context story (the cross-device half is
tpu_dist.parallel.ring_attention, which rotates KV blocks over ICI with the
same online-softmax recurrence): Q/K/V tiles stream HBM -> VMEM, scores for
one (block_q, block_k) tile live only in VMEM/registers, and the softmax is
accumulated online (flash recurrence), so memory is O(T) instead of O(T^2).

Layout (kernel-internal): (BH, T, D) with a (BH, nq, nk) grid; the KV index
is innermost so the f32 accumulators (m, l, acc) persist in VMEM scratch
across a Q row's KV sweep and the output tile is written back to HBM once.
Forward saves per-row logsumexp; backward recomputes score tiles from
(q, k, lse) flash-style — two kernels, one accumulating dQ over the KV
sweep, one accumulating dK/dV over the Q sweep (grid transposed so the
accumulators stay resident).  Residuals are just (q, k, v, o, lse): no
(Tq, Tk) tensor is ever materialized, forward or backward.

Causal masking is applied per-tile from global positions; tiles entirely
above the diagonal are predicated off with ``pl.when`` (no MXU work, the
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

__all__ = ["flash_attention", "flash_attention_with_lse"]

_LANE = 128
_D_ALIGN = 64  # head_dim alignment: 64 halves K/V DMA for d=64 vs padding to 128
_NEG_INF = -1e30  # finite: keeps max/correction arithmetic NaN-free when a
                  # whole tile is masked (same sentinel as ring_attention)


def _clamp_blocks(dtype, tq, tk, block_q, block_k):
    """Tile sizes that fit VMEM: the 1024 defaults are tuned for bf16; with
    f32 inputs the tile intermediates double and the dK/dV kernel's
    (block_q, block_k) f32 score/prob/ds tiles blow the ~16 MB VMEM budget
    at 1024² (observed: 16.17M > 16M on v5e) — halve for 4-byte dtypes."""
    if jnp.dtype(dtype).itemsize >= 4:
        block_q = min(block_q, 512)
        block_k = min(block_k, 512)
    return (min(block_q, _ceil_to(tq, _LANE)),
            min(block_k, _ceil_to(tk, _LANE)))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _masked_scores(q, k, sm_scale, tk, causal, q_lo, k_lo):
    """(block_q, block_k) score tile on the MXU (f32 accumulation), with
    out-of-range and above-diagonal entries set to _NEG_INF.  The single
    source of the score/mask convention shared by the forward and both
    backward kernels.

    ``causal`` is three-valued: ``True`` masks above the diagonal,
    ``False`` doesn't, and ``"offdiag"`` also doesn't — its tiles sit
    strictly below the diagonal band by the grid predicate, so per-element
    causal mask math (two iotas + compare + select per tile) is skipped
    entirely; only the K padding range check remains."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < tk
    if causal is True:
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        mask = mask & (kpos <= qpos)
    return jnp.where(mask, s, _NEG_INF), mask


def _tile_live(causal, q_lo, k_lo, block_q, block_k):
    """Grid predicate: does tile (q_lo, k_lo) contribute any unmasked
    entries?  ``True`` = tiles intersecting or below the diagonal;
    ``"offdiag"`` = tiles STRICTLY below the diagonal band (the masked
    diagonal tiles are handled by a separate finer-tiled causal call —
    see _split_lse); ``False`` = all tiles."""
    if causal is True:
        return k_lo <= q_lo + block_q - 1
    if causal == "offdiag":
        return k_lo + block_k <= q_lo
    return k_lo >= 0  # trivially true (kernel body must sit under pl.when)


def _tile_probs(q_ref, k_ref, lse_ref, sm_scale, tk, causal, q_lo, k_lo):
    """Recompute the softmax probabilities of one tile from (q, k, lse) —
    the flash-backward recurrence shared by the dQ and dK/dV kernels."""
    s, mask = _masked_scores(q_ref[0], k_ref[0], sm_scale, tk, causal,
                             q_lo, k_lo)
    p = jnp.exp(s - lse_ref[0])                             # (bq, bk) f32
    return jnp.where(mask, p, 0.0)


def _make_fwd_kernel(sm_scale, tk, block_q, block_k, causal):
    from jax.experimental import pallas as pl

    def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr):
        qi = pl.program_id(1)
        ki = pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(ki == 0)
        def _init():
            m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

        q_lo = qi * block_q
        k_lo = ki * block_k

        def body():
            s, mask = _masked_scores(q_ref[0], k_ref[0], sm_scale, tk,
                                     causal, q_lo, k_lo)
            m_prev = m_scr[:, 0:1]
            l_prev = l_scr[:, 0:1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            # fully-masked rows: s == m_new == _NEG_INF gives exp(0) = 1;
            # zero them so they contribute nothing
            p = jnp.where(mask, p, 0.0)
            l_scr[:] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
                l_scr.shape)
            v = v_ref[0]
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_scr[:] = acc_scr[:] * alpha + pv
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

        # tiles contributing nothing (above the diagonal / diagonal band)
        # are predicated off; non-causal uses a trivially-true predicate
        # (see _use_interpret for why the body must be under pl.when
        # either way)
        @pl.when(_tile_live(causal, q_lo, k_lo, block_q, block_k))
        def _():
            body()

        @pl.when(ki == nk - 1)
        def _fin():
            m = m_scr[:, 0:1]
            l = l_scr[:, 0:1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
            lse_ref[0] = m + jnp.log(l_safe)

    return kernel


def _fwd_call(q, k, v, causal, sm_scale, block_q, block_k):
    """q: (BH, Tq, D); k, v: (BH, Tk, D) -> (o, lse) with lse (BH, Tq, 1)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q, block_k = _clamp_blocks(q.dtype, tq, tk, block_q, block_k)
    tqp, tkp, dp = _ceil_to(tq, block_q), _ceil_to(tk, block_k), _ceil_to(d, _D_ALIGN)
    qp = jnp.pad(q, ((0, 0), (0, tqp - tq), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, tkp - tk), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, tkp - tk), (0, dp - d)))
    grid = (bh, tqp // block_q, tkp // block_k)
    o, lse = pl.pallas_call(
        _make_fwd_kernel(sm_scale, tk, block_q, block_k, causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct((bh, tqp, dp), q.dtype, qp, kp, vp),
            _out_struct((bh, tqp, 1), jnp.float32, qp, kp, vp),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running max m
            pltpu.VMEM((block_q, _LANE), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, dp), jnp.float32),      # output accumulator
        ],
        interpret=_use_interpret(),
        name="flash_fwd",
    )(qp, kp, vp)
    return o[:, :tq, :d], lse[:, :tq]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _make_dq_kernel(sm_scale, tk, block_q, block_k, causal):
    from jax.experimental import pallas as pl

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr):
        qi = pl.program_id(1)
        ki = pl.program_id(2)
        nk = pl.num_programs(2)

        @pl.when(ki == 0)
        def _init():
            acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

        q_lo = qi * block_q
        k_lo = ki * block_k

        def body():
            # q/k/v/do stay in their input dtype: bf16 inputs run bf16 MXU
            # passes with f32 accumulation (preferred_element_type)
            k = k_ref[0]
            v = v_ref[0]
            do = do_ref[0]
            p = _tile_probs(q_ref, k_ref, lse_ref, sm_scale, tk, causal,
                            q_lo, k_lo)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0])).astype(k.dtype)  # (bq, bk)
            acc_scr[:] = acc_scr[:] + sm_scale * jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(_tile_live(causal, q_lo, k_lo, block_q, block_k))
        def _():
            body()

        @pl.when(ki == nk - 1)
        def _fin():
            dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)

    return kernel


def _make_dkv_kernel(sm_scale, tk, block_q, block_k, causal):
    from jax.experimental import pallas as pl

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dk_ref, dv_ref, dk_scr, dv_scr):
        ki = pl.program_id(1)
        qi = pl.program_id(2)
        nq = pl.num_programs(2)

        @pl.when(qi == 0)
        def _init():
            dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
            dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

        q_lo = qi * block_q
        k_lo = ki * block_k

        def body():
            q = q_ref[0]
            v = v_ref[0]
            do = do_ref[0]
            p = _tile_probs(q_ref, k_ref, lse_ref, sm_scale, tk, causal,
                            q_lo, k_lo)
            # padded q rows contribute nothing: their do and delta are zero
            dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0])).astype(q.dtype)
            dk_scr[:] = dk_scr[:] + sm_scale * jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(_tile_live(causal, q_lo, k_lo, block_q, block_k))
        def _():
            body()

        @pl.when(qi == nq - 1)
        def _fin():
            dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    return kernel


def _bwd_call(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k,
              dlse=None, delta=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q, block_k = _clamp_blocks(q.dtype, tq, tk, block_q, block_k)
    tqp, tkp, dp = _ceil_to(tq, block_q), _ceil_to(tk, block_k), _ceil_to(d, _D_ALIGN)

    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian correction term;
    # cheap elementwise jnp, fused by XLA around the kernels.  When the
    # caller differentiates through lse too (ring-attention merge), its
    # cotangent enters the same place with opposite sign:
    # dL/ds_ij = p_ij * (dp_ij - delta_i + dlse_i), so fold it into delta.
    # The split-causal backward passes a precomputed ``delta`` so its two
    # region calls share one rowsum pass.
    if delta is None:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)              # (BH, Tq, 1)
        if dlse is not None:
            delta = delta - dlse.astype(jnp.float32)

    qp = jnp.pad(q, ((0, 0), (0, tqp - tq), (0, dp - d)))
    kp = jnp.pad(k, ((0, 0), (0, tkp - tk), (0, dp - d)))
    vp = jnp.pad(v, ((0, 0), (0, tkp - tk), (0, dp - d)))
    dop = jnp.pad(do, ((0, 0), (0, tqp - tq), (0, dp - d)))
    lsep = jnp.pad(lse, ((0, 0), (0, tqp - tq), (0, 0)))
    deltap = jnp.pad(delta, ((0, 0), (0, tqp - tq), (0, 0)))

    q_spec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec_dq = pl.BlockSpec((1, block_k, dp), lambda b, i, j: (b, j, 0),
                              memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        _make_dq_kernel(sm_scale, tk, block_q, block_k, causal),
        grid=(bh, tqp // block_q, tkp // block_k),
        in_specs=[q_spec, kv_spec_dq, kv_spec_dq, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=_out_struct((bh, tqp, dp), q.dtype, qp, kp, vp, dop),
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        interpret=_use_interpret(),
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lsep, deltap)

    # grid transposed: KV tile outer, Q sweep inner, so dk/dv accumulate
    q_spec_t = pl.BlockSpec((1, block_q, dp), lambda b, j, i: (b, i, 0),
                            memory_space=pltpu.VMEM)
    kv_spec_t = pl.BlockSpec((1, block_k, dp), lambda b, j, i: (b, j, 0),
                             memory_space=pltpu.VMEM)
    row_spec_t = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0),
                              memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        _make_dkv_kernel(sm_scale, tk, block_q, block_k, causal),
        grid=(bh, tkp // block_k, tqp // block_q),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[_out_struct((bh, tkp, dp), k.dtype, qp, kp, vp, dop),
                   _out_struct((bh, tkp, dp), v.dtype, qp, kp, vp, dop)],
        scratch_shapes=[pltpu.VMEM((block_k, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32)],
        interpret=_use_interpret(),
        name="flash_bwd_dkv",
    )(qp, kp, vp, dop, lsep, deltap)
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
    o, lse = _fwd_call(q, k, v, causal, sm_scale, block_q, block_k)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd(causal, sm_scale, block_q, block_k, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _bwd_call(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k,
                     dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _merge_lse(o_a, lse_a, o_b, lse_b):
    """Exact blockwise-attention merge of two partial results over disjoint
    KV sets (the identity from flash_attention_with_lse's docstring), in
    f32.  Plain jnp: autodiff routes the cotangents into both partials'
    custom VJPs (including dlse), exactly like ring_attention's merge."""
    m = jnp.maximum(lse_a, lse_b)
    w_a = jnp.exp(lse_a - m)
    w_b = jnp.exp(lse_b - m)
    den = w_a + w_b
    o = (o_a.astype(jnp.float32) * w_a + o_b.astype(jnp.float32) * w_b) / den
    return o.astype(o_a.dtype), m + jnp.log(den)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _split_lse(q, k, v, sm_scale, block_q, block_k):
    """Causal flash attention as two kernel calls per pass whose executed
    tile area ≈ the useful (unmasked) score area.

    A single causal call sweeps every tile touching the diagonal with
    full-size blocks, so at seq = 2·block the three executed 1024² tiles
    are only 2/3 useful (the two diagonal tiles are half masked).  Split
    instead:

    - **off-diagonal**: tiles STRICTLY below the diagonal band (mode
      ``"offdiag"``) — full blocks, zero masked area, and no per-element
      causal mask math at all;
    - **diagonal band**: each q block attends causally within its own
      band, which is exactly a BATCHED causal attention over
      (BH·n_bands, block_q) sequences — the same kernel at half-size
      blocks, so the masked waste per band shrinks from block²/2 to
      block²/4 (minus the skipped above-diagonal sub-tile);

    merged with the exact blockwise-lse identity.  Executed-area ratio vs
    the single call: (n² + n/2) / (n² + n) per n = T/block — a 1/6 area
    cut at n=2, vanishing as n grows (the 8k curve point was already
    ~90% useful).  Measured on the v5e the area cut does NOT convert to
    time on a quiet chip: at 2048 the single call is bound by grid-step
    overhead (~1.9 us/step), and the split triples the step count, so it
    only wins under heavy chip contention (1.7-2.5x there, 0.3-0.5x
    quiet) — hence opt-in, see flash_attention_with_lse.

    The custom VJP is at THIS level, not composed from two _flash_lse
    VJPs: the backward recomputes p = exp(s - lse) from the MERGED lse in
    both regions (the standard flash recurrence is oblivious to how the
    forward was tiled), so the residuals are exactly the single-call ones
    (q, k, v, o, lse) — composing custom-VJP calls through the merge
    instead saves two extra partial (o, lse) pairs and differentiates the
    elementwise merge, which measured as a complete wash at 2048.

    Inputs are the kernel-internal (BH, T, D) layout; requires tq == tk
    and block_q | tq (the dispatch condition in
    flash_attention_with_lse)."""
    return _split_fwd_impl(q, k, v, sm_scale, block_q, block_k)


def _to_bands(x, n_bands, band):
    bh = x.shape[0]
    return x.reshape(bh * n_bands, band, x.shape[-1])


def _split_fwd_impl(q, k, v, sm_scale, block_q, block_k):
    bh, tq, d = q.shape
    n_bands = tq // block_q
    o_diag, lse_diag = _fwd_call(
        _to_bands(q, n_bands, block_q), _to_bands(k, n_bands, block_q),
        _to_bands(v, n_bands, block_q), True, sm_scale,
        block_q // 2, block_q // 2)
    o_off, lse_off = _fwd_call(q, k, v, "offdiag", sm_scale,
                               block_q, block_k)
    return _merge_lse(o_off, lse_off, o_diag.reshape(bh, tq, d),
                      lse_diag.reshape(bh, tq, 1))


def _split_fwd(q, k, v, sm_scale, block_q, block_k):
    o, lse = _split_fwd_impl(q, k, v, sm_scale, block_q, block_k)
    return (o, lse), (q, k, v, o, lse)


def _split_bwd(sm_scale, block_q, block_k, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    bh, tq, d = q.shape
    n_bands = tq // block_q
    # one shared softmax-jacobian correction (see _bwd_call): both region
    # calls recompute p from the same merged lse, so they share delta too
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    dq_off, dk_off, dv_off = _bwd_call(
        q, k, v, o, lse, do, "offdiag", sm_scale, block_q, block_k,
        delta=delta)

    def bands(x):
        return _to_bands(x, n_bands, block_q)

    dq_d, dk_d, dv_d = _bwd_call(
        bands(q), bands(k), bands(v), bands(o), bands(lse), bands(do),
        True, sm_scale, block_q // 2, block_q // 2, delta=bands(delta))
    return (dq_off + dq_d.reshape(bh, tq, d),
            dk_off + dk_d.reshape(bh, tq, d),
            dv_off + dv_d.reshape(bh, tq, d))


_split_lse.defvjp(_split_fwd, _split_bwd)


def flash_attention_with_lse(q, k, v, causal: bool = False, sm_scale=None,
                             block_q: int = 1024, block_k: int = 1024,
                             split_diag=None):
    """Flash attention returning ``(out, lse)``.

    ``out``: (..., Tq, H, D) like :func:`flash_attention`; ``lse``:
    (..., Tq, H) float32 per-row logsumexp of the scaled scores.  Partial
    results ``(out_a, lse_a), (out_b, lse_b)`` over disjoint KV blocks merge
    exactly (the blockwise-attention identity used by
    tpu_dist.parallel.ring_attention)::

        m = max(lse_a, lse_b); w = exp(lse_? - m)
        out = (out_a*w_a + out_b*w_b) / (w_a + w_b); lse = m + log(w_a + w_b)

    Differentiable in both outputs (the lse cotangent folds into the
    softmax-jacobian correction).  Rows with no visible keys get lse ≈ -1e30
    and out 0 — the merge weight exp(lse - m) then vanishes exactly.
    """
    if q.ndim < 3:
        raise ValueError(f"expected (..., T, H, D), got {q.shape}")
    *lead, tq, h, d = q.shape
    tk = k.shape[-3]
    if not (q.shape[:-3] == k.shape[:-3] == v.shape[:-3]
            and k.shape[-2:] == v.shape[-2:] == (h, d)
            and v.shape[-3] == tk):
        # no numpy-broadcast batch semantics here: the (B*H, T, D) flatten
        # would silently misalign batches — use impl='dense' for shared KV
        raise ValueError(
            f"flash_attention needs identical batch/head dims for q, k, v; "
            f"got q={q.shape}, k={k.shape}, v={v.shape}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if not isinstance(causal, str):
        # normalize truthy values (np.bool_, 1) to the literal bool the
        # kernels' three-valued dispatch (`causal is True`) relies on
        causal = bool(causal)

    def to3(x, t):
        x = x.reshape(-1, t, h, d)
        return jnp.swapaxes(x, 1, 2).reshape(-1, t, d)

    # ``split_diag`` is OPT-IN (default off).  The two-call split
    # (_split_lse) makes executed tile area ≈ useful area, and interleaved
    # A/B under heavy chip contention measured it 1.7-2.5x faster at seq
    # 2048 — but on a QUIET chip the same A/B inverts (0.3-0.5x): at 2048
    # the single call is grid-overhead-bound, not area-bound (128 grid
    # steps at ~1.9 us vs the split's ~384 across its finer-tiled calls),
    # and 1024^2 single-call already runs at the same per-executed-area
    # rate as 8k there (142 TF fwd reported / (4/3) accounting inflation
    # ~= 107 effective ~= the 8k row).  Quiet windows are what the
    # best-ever ratchet keeps, so the split stays a documented variant
    # (exact numerics, tests/test_flash_attention.py), not the default.
    bq_eff, bk_eff = _clamp_blocks(q.dtype, tq, tk, block_q, block_k)
    if split_diag is None:
        split_diag = False
    elif split_diag:
        # explicit opt-in: the split hardcodes causal self-attention
        # semantics, so reject configurations it would silently get wrong
        if causal is not True or tq != tk or tq % bq_eff:
            raise ValueError(
                "split_diag=True requires causal=True self-attention "
                f"(tq == tk) with block_q dividing tq; got causal={causal}, "
                f"tq={tq}, tk={tk}, effective block_q={bq_eff}")
        # the off-diagonal predicate (k_lo + block_k <= q_lo) skips key
        # columns outright if k tiles are coarser than the q banding —
        # square tiles are the only layout the split supports
        bk_eff = bq_eff
    if split_diag:
        o3, lse3 = _split_lse(to3(q, tq), to3(k, tk), to3(v, tk),
                              float(sm_scale), bq_eff, bk_eff)
    else:
        o3, lse3 = _flash_lse(to3(q, tq), to3(k, tk), to3(v, tk), causal,
                              float(sm_scale), int(block_q), int(block_k))
    o = jnp.swapaxes(o3.reshape(-1, h, tq, d), 1, 2).reshape(*lead, tq, h, d)
    lse = jnp.swapaxes(lse3.reshape(-1, h, tq), 1, 2)       # (B, Tq, H)
    return o, lse.reshape(*lead, tq, h)


def flash_attention(q, k, v, causal: bool = False, sm_scale=None,
                    block_q: int = 1024, block_k: int = 1024,
                    split_diag=None):
    """Flash attention.  ``q``: (..., Tq, H, D); ``k, v``: (..., Tk, H, D).

    Drop-in for :func:`tpu_dist.nn.attention.scaled_dot_product_attention`
    (mask=None); differentiable; O(T) memory.  ``block_q``/``block_k`` are
    VMEM tile sizes (auto-clamped for short sequences).  The 1024 defaults
    are from an on-chip sweep at (4, 8192, 8, 64) bf16 causal: large tiles
    amortize grid/DMA overhead and win ~2.5x over 128 tiles for training
    (fwd+bwd); measured vs jax.experimental.pallas.ops.tpu.flash_attention
    at the same shape this kernel is ~2x (fwd) / ~4x (fwd+bwd) faster.

    Same computation as :func:`flash_attention_with_lse` with the lse
    discarded (its cotangent is then zero, so the backward is identical).
    """
    return flash_attention_with_lse(q, k, v, causal=causal,
                                    sm_scale=sm_scale, block_q=block_q,
                                    block_k=block_k,
                                    split_diag=split_diag)[0]

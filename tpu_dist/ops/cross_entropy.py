"""Fused softmax cross-entropy — Pallas TPU kernel with custom VJP.

The loss of every reference workload (`nn.CrossEntropyLoss`,
/root/reference/mpspawn_dist.py:63, example_mp.py:83).  The composed jnp
version (tpu_dist.nn.functional.cross_entropy) materializes log-softmax
(B, V) in HBM between ops; this kernel keeps each row block resident in
VMEM and emits only the per-row loss — one HBM read of the logits forward,
one read + one write backward.  Matters when V is large (LM heads), not for
V=10 image classifiers; `nn.CrossEntropyLoss(fused=True)` opts in.

Layout: grid over row blocks of one sublane tile of the logits' dtype (8
rows of f32, 16 of bf16); each kernel invocation sees the full
(padded-to-lane) vocab row.  Forward saves per-row logsumexp; backward
recomputes softmax from (logits, lse) — no (B, V) residual beyond the
logits themselves.

Runs on TPU via Mosaic; everywhere else (CPU tests) through
``interpret=True`` — same kernel, same numerics (tests compare against the
jnp composition and torch's own CrossEntropyLoss).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._pallas import (ceil_to as _ceil_to, out_struct as _out_struct,
                      sublane_tile as _sublane_tile,
                      use_interpret as _use_interpret)

__all__ = ["fused_cross_entropy"]

_LANE = 128


def _fwd_kernel(logits_ref, labels_ref, nll_ref, lse_ref, *, vocab: int):
    # body predicated on a trivially-true condition: the HLO interpreter's
    # discharge of a bare body trips shard_map's varying-axes check (see
    # _pallas.use_interpret) and this kernel runs under the DDP wrapper's
    # shard_map when CrossEntropyLoss(fused=True) is used
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) >= 0)
    def _():
        logits = logits_ref[:].astype(jnp.float32)       # (tile, Vpad)
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        valid = cols < vocab
        logits = jnp.where(valid, logits, -jnp.inf)
        mx = jnp.max(logits, axis=1, keepdims=True)      # (tile, 1)
        shifted = logits - mx
        sumexp = jnp.sum(jnp.where(valid, jnp.exp(shifted), 0.0), axis=1,
                         keepdims=True)
        lse = mx + jnp.log(sumexp)                       # (tile, 1)
        onehot = cols == labels_ref[:]                   # (tile, Vpad)
        picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=1,
                         keepdims=True)
        nll_ref[:] = lse - picked
        lse_ref[:] = lse


def _bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, dlogits_ref, *,
                vocab: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) >= 0)
    def _():
        logits = logits_ref[:].astype(jnp.float32)
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        valid = cols < vocab
        p = jnp.where(valid, jnp.exp(logits - lse_ref[:]), 0.0)
        onehot = (cols == labels_ref[:]) & valid
        dlogits_ref[:] = ((p - onehot.astype(jnp.float32)) * g_ref[:]
                          ).astype(dlogits_ref.dtype)


def _pad(logits, labels):
    """Pad rows to the dtype's sublane tile and the vocab to the lane
    width; returns ``(logits, labels, rows_padded, vocab_padded, tile)``."""
    b, v = logits.shape
    tile = _sublane_tile(logits.dtype)
    bp, vp = _ceil_to(b, tile), _ceil_to(v, _LANE)
    if (bp, vp) != (b, v):
        logits = jnp.pad(logits, ((0, bp - b), (0, vp - v)))
        labels = jnp.pad(labels, (0, bp - b))
    return logits, labels, bp, vp, tile


def _call_fwd(logits, labels):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, v = logits.shape
    logits_p, labels_p, bp, vp, tile = _pad(logits, labels)
    labels2d = labels_p.astype(jnp.int32)[:, None]       # (Bp, 1)
    grid = (bp // tile,)
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, vocab=v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, vp), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((tile, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _out_struct((bp, 1), jnp.float32, logits_p, labels2d),
            _out_struct((bp, 1), jnp.float32, logits_p, labels2d),
        ],
        interpret=_use_interpret(),
        name="fused_ce_fwd",
    )(logits_p, labels2d)
    return nll[:b, 0], lse[:b, 0]


def _call_bwd(logits, labels, lse, g_rows):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, v = logits.shape
    logits_p, labels_p, bp, vp, tile = _pad(logits, labels)
    labels2d = labels_p.astype(jnp.int32)[:, None]
    lse2d = jnp.pad(lse, (0, bp - b))[:, None]
    g2d = jnp.pad(g_rows, (0, bp - b))[:, None].astype(jnp.float32)
    grid = (bp // tile,)
    dlogits = pl.pallas_call(
        functools.partial(_bwd_kernel, vocab=v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, vp), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, 1), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, vp), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_out_struct((bp, vp), logits.dtype, logits_p, labels2d,
                              lse2d, g2d),
        interpret=_use_interpret(),
        name="fused_ce_bwd",
    )(logits_p, labels2d, lse2d, g2d)
    return dlogits[:b, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _fused_nll(logits, labels):
    nll, _ = _call_fwd(logits, labels)
    return nll


def _fused_nll_fwd(logits, labels):
    nll, lse = _call_fwd(logits, labels)
    return nll, (logits, labels, lse)


def _fused_nll_bwd(res, g):
    logits, labels, lse = res
    return _call_bwd(logits, labels, lse, g), None


_fused_nll.defvjp(_fused_nll_fwd, _fused_nll_bwd)


def fused_cross_entropy(logits, labels, reduction: str = "mean"):
    """Drop-in for :func:`tpu_dist.nn.functional.cross_entropy`, computed by
    the Pallas kernel.  ``logits``: (..., V); ``labels``: integer (...)."""
    v = logits.shape[-1]
    flat_logits = logits.reshape(-1, v)
    flat_labels = labels.reshape(-1)
    nll = _fused_nll(flat_logits, flat_labels)
    if reduction == "mean":
        return nll.mean()
    if reduction == "sum":
        return nll.sum()
    if reduction == "none":
        return nll.reshape(labels.shape)
    raise ValueError(f"Unknown reduction {reduction!r}")

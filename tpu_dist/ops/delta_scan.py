"""The gated delta rule's chunked scan — a Pallas TPU kernel that keeps a
chunk's 64 x 64 system, its products and the carried state in VMEM.

A prefill of a recurrent layer whose decay is ONE number a head a token
(tpu_dist/nn/deltanet.py: :class:`~tpu_dist.nn.GatedDeltaNet`) advances each
value head's ``(Dk, Dv)`` float32 state over the prompt in chunks of 64
positions, ``gated_delta_chunked``'s WY form.  Per chunk, with ``G`` the log
decay summed within the chunk and ``decay[i, j] = exp(G_i - G_j)`` for ``j <=
i``::

    a = -(beta k) k^T * decay, strictly lower;   inv = (I - a)^-1
    value = inv (beta v);   k_cum = inv (beta e^G k)
    v_new = value - k_cum S
    o = (e^G q) S + (q k^T * decay, lower) v_new
    S' = e^(G_last) S + (e^(G_last - G) k)^T v_new

As ``jax.numpy`` every intermediate of shape ``(.., C, C)`` and ``(.., C,
128)`` goes out to HBM between XLA's fusions, the inverse's six doubling
products over all chunks at once and the carry's operands moved chunks-first
among them: a third of a long prompt's device time (PERF.md, PRs 30, 42).
The dependency inside a chunk is one no fusion of two HLO operations
expresses.  Here:

- the grid is ``(rows, groups of value heads, groups of chunks)``, the chunks
  last and in order: the heads' states live in a VMEM scratch across that
  axis, read from the cache leaf at a head's first chunk and written after
  its last, over their input (``input_output_aliases``: donate the state);
- ``q``, ``k``, ``v`` and the output are read and written in the layout the
  layer has them, ``(B, T, heads x D)``, a ``(C, D)`` tile a head by the
  index map: no transpose to heads-first, and ``q``, ``k`` BY KEY HEAD
  (value head ``h`` reads key head ``h // (Hv / Hk)``), never repeated;
- the small per-position operands arrive with the heads side by side in the
  lanes: ``[G | beta]`` as ``(B, T, 2 Hv)``, a head's column taken by a
  masked lane sum, and ``G`` once more positions-last, two heads' rows side
  by side, for the row of ``G_i - G_j``.  Shaped ``(.., C, 1)`` each would
  pad its one lane to 128;
- value heads go in PAIRS: the two heads' ``(C, C)`` matrices stand side by
  side in the lanes, ``[m_0 | m_1]`` of ``(C, 2 C)``, so the elementwise work
  on them fills whole vector registers, and ``[x_0 | x_1]`` by the block
  diagonal ``[[y_0, 0], [0, y_1]]`` is both heads' products in ONE full
  128 x 128 tile of the MXU where each alone fills a quarter;
- the inverse is the doubling of ``gated_delta_chunked`` with the running
  sum on the LEFT (powers of one matrix commute): ``[S_j; p_j] p_j`` is one
  product a step; and it is applied ONCE, to ``beta (v - e^G (k S))``: the
  WY form's ``value - k_cum S`` with one product where it has three;
- every product is three bfloat16 passes over float32 operands split into
  high and low halves by hand (``lax.Precision.HIGH``'s arithmetic, which
  Mosaic's ``dot`` does not offer: it has one pass or six), each operand
  split once, the three passes ONE product whose contraction is three times
  as long, so that they add up inside the MXU's float32 accumulator;
- a step holds ``PAIRS_A_STEP`` pairs and ``CHUNKS_A_STEP`` chunks, and every
  stage is written over all of them before the next.  One chunk's products
  are a chain, each waiting on the last; a pair's chains do not depend on
  another pair's, nor a chunk's inverse on another chunk's, and the compiler
  overlaps what stands close in the program (timed on the chip, PERF.md, PR
  42: 2.76 ms a call of the cell's layer at one pair and one chunk a step,
  1.55 at two and four).

A position with ``g = 0`` and ``beta = 0`` is the recurrence's no-op, a
whole chunk of them leaves the state bit for bit.  No backward: the
differentiable forward keeps the ``jax.numpy`` form.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ._pallas import out_struct as _out_struct, use_interpret as _use_interpret
from .delta_step import _LANE, delta_step_ok

__all__ = ["delta_scan", "delta_scan_ok"]

CHUNK = 64
# Chunks, and pairs of value heads, a grid step holds where the call's shapes
# allow (the module docstring's last point).  Timed on the chip at the hybrid
# cell's layer (PERF.md, PR 42): 2 x 4 1.55 ms a call, 2 x 2 1.56, 4 x 2 1.49,
# 1 x 4 1.83, 1 x 8 1.81, 2 x 1 1.85, 1 x 1 2.76.
CHUNKS_A_STEP = 4
PAIRS_A_STEP = 2
_NT = ((1,), (1,))          # x y^T
_NN = ((1,), (0,))          # x y
_TN = ((0,), (0,))          # x^T y


def delta_scan_ok(state) -> bool:
    """Whether the kernel takes this state leaf: float32 ``(B, H, Dk, Dv)``
    of an even number of heads (a grid step holds two) whose ``Dk`` and
    ``Dv`` both fill whole lanes (a head's ``q``, ``k``, ``v`` tiles are
    lane blocks of ``(B, T, heads x D)``)."""
    return (delta_step_ok(state) and state.shape[1] % 2 == 0
            and state.shape[-2] % _LANE == 0)


def _split(x):
    """A float32 array as the sum of two bfloat16 halves (to 2^-17), the
    pair a product takes."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot(x, y, dims=_NN):
    """``x`` by ``y`` (each a pair from :func:`_split`) in three bfloat16
    passes, ``x_hi y_lo + x_lo y_hi + x_hi y_hi``, as ONE product whose
    contraction is three times as long: the passes add up inside the MXU's
    accumulator."""
    (xh, xl), (yh, yl) = x, y
    (cx,), (cy,) = dims
    return lax.dot_general(
        jnp.concatenate([xh, xl, xh], axis=cx),
        jnp.concatenate([yl, yh, yh], axis=cy), (dims, ((), ())),
        preferred_element_type=jnp.float32)


def _both(f, *pairs):
    """``f`` of the high halves and of the low halves."""
    return tuple(f(*halves) for halves in zip(*pairs))


def _diagonal(x, width):
    """``[x_0 | x_1]`` (each ``width`` lanes; a pair from :func:`_split`)
    as ``[[x_0, 0], [0, x_1]]``."""
    left = lax.broadcasted_iota(jnp.int32, x[0].shape, 1) < width
    zero = jnp.zeros((), x[0].dtype)
    return _both(lambda a: jnp.concatenate(
        [jnp.where(left, a, zero), jnp.where(left, zero, a)], axis=0), x)


def _kernel(s_ref, q_ref, k_ref, v_ref, cols_ref, rows_ref, o_ref, so_ref,
            state, *, heads, rep, pairs):
    from jax.experimental import pallas as pl

    # read outside the conditionals: the interpreter binds them there alone
    n, steps = pl.program_id(2), pl.num_programs(2)
    first = 2 * pairs * pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state[...] = s_ref[0]

    @pl.when(n >= 0)
    def _():
        c = CHUNK
        dk, dv = state.shape[1:]
        chunks = q_ref.shape[1] // c
        # The (C, C) matrices of a pair of heads stand side by side in the
        # lanes, (C, 2 C): [m_0 | m_1] by the block diagonal of another such
        # is both heads' products in one full tile of the MXU.
        i = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
        lane = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
        left = lane < c
        j = jnp.where(left, lane, lane - c)
        cols = cols_ref[0]                                   # (m C, 2 Hv)
        at = lax.broadcasted_iota(jnp.int32, cols.shape, 1)
        column = lambda h: jnp.sum(jnp.where(at == h, cols, 0.0),
                                   axis=1, keepdims=True)
        g_cols = [column(first + h) for h in range(2 * pairs)]   # (m C, 1)
        beta_cols = [column(heads + first + h) for h in range(2 * pairs)]
        rows_cat = lambda *xs: _both(
            lambda *a: jnp.concatenate(a, axis=0), *xs)
        top = lambda x: _both(lambda a: a[:c], x)

        # Every stage below is written over ALL the step's (pair, chunk)
        # units before the next: the units' chains of products do not depend
        # on one another, and the compiler overlaps what stands close.
        units = [(p, m) for m in range(chunks) for p in range(pairs)]
        rows = lambda m: slice(m * c, (m + 1) * c)

        def keys_of(p):
            """The lanes of the step's ``q`` / ``k`` block that are pair
            ``p``'s key head (``rep`` > 1: its two value heads share one),
            or its two key heads."""
            if rep == 1:
                return slice(2 * p * dk, (2 * p + 2) * dk)
            return slice(2 * p // rep * dk, (2 * p // rep + 1) * dk)

        k = [k_ref[0, rows(m), keys_of(p)] for p, m in units]
        q = [q_ref[0, rows(m), keys_of(p)] for p, m in units]
        g = [[g_cols[2 * p + r][rows(m)] for r in range(2)]
             for p, m in units]
        beta = [[beta_cols[2 * p + r][rows(m)] for r in range(2)]
                for p, m in units]
        kq = [_split(jnp.concatenate(x, axis=0)) for x in zip(k, q)]
        # [[k k^T | k k^T], [q k^T | q k^T]], each half its head's
        keys = [rows_cat(top(x), top(x)) if rep > 1 else _diagonal(top(x), dk)
                for x in kq]
        both = [_dot(x, y, _NT) for x, y in zip(kq, keys)]
        decay = [jnp.exp(jnp.where(
            i >= j, jnp.where(left, *gs) - rows_ref[0, m, 0, p:p + 1, :],
            -jnp.inf))
            for gs, (p, m) in zip(g, units)]
        a = [jnp.where(i > j, -(jnp.where(left, *bs) * x[:c]) * d, 0.0)
             for bs, x, d in zip(beta, both, decay)]
        within = [_split(jnp.where(i >= j, x[c:] * d, 0.0))
                  for x, d in zip(both, decay)]
        # (I - a)^-1 = I + a + ... + a^(C-1): S_1 = I + a, p_1 = a a,
        # S_(j+1) = S_j + S_j p_j, p_(j+1) = p_j p_j
        inv = [jnp.where(i == j, 1.0, 0.0) + x for x in a]
        halves = [_split(x) for x in a]
        power = [_dot(x, _diagonal(x, c)) for x in halves]
        doublings = max(c - 1, 1).bit_length() - 1
        for step in range(doublings):
            halves = [_split(x) for x in power]
            by = [_diagonal(x, c) for x in halves]
            if step == doublings - 1:
                inv = [x + _dot(_split(x), y) for x, y in zip(inv, by)]
            else:
                two = [_dot(rows_cat(_split(x), h), y)
                       for x, h, y in zip(inv, halves, by)]
                inv = [x + t[:c] for x, t in zip(inv, two)]
                power = [t[c:] for t in two]
        inv = [_split(x) for x in inv]

        # The chain, chunk by chunk, the step's pairs side by side: v_new =
        # inv (beta (v - e^G (k S))), the WY form's ``value - k_cum S`` with
        # the inverse applied once; the chunk's output; the state after it.
        zero = jnp.zeros((c, dv), jnp.bfloat16)
        lanes_cat = lambda a, b: jnp.concatenate([a, b], axis=1)
        # [[x_0, 0], [0, x_1]] of two heads' (C, Dv)
        apart = lambda x0, x1: jnp.concatenate(
            [lanes_cat(x0, zero), lanes_cat(zero, x1)], axis=0)
        for m in range(chunks):
            of = [m * pairs + p for p in range(pairs)]
            s = [[state[2 * p + r] for r in range(2)] for p in range(pairs)]
            if rep > 1:
                read = [_dot(kq[u], _split(jnp.concatenate(s[p], axis=1)))
                        for p, u in enumerate(of)]
                reads = [[x[:, :dv], x[:, dv:]] for x in read]
            else:
                reads = [[_dot(_both(lambda x: x[:, r * dk:(r + 1) * dk],
                                     kq[u]), _split(s[p][r]))
                          for r in range(2)] for p, u in enumerate(of)]
            e = [[jnp.exp(x) for x in g[u]] for u in of]
            rest = [[_split(beta[u][r] * (
                v_ref[0, rows(m), (2 * p + r) * dv:(2 * p + r + 1) * dv]
                - e[p][r] * reads[p][r][:c])) for r in range(2)]
                for p, u in enumerate(of)]
            v_new = [_dot(inv[u], _both(apart, *rest[p]))
                     for p, u in enumerate(of)]
            fresh = [_split(x) for x in v_new]               # (C, 2 Dv)
            ahead = [_dot(within[u], _both(
                lambda x: apart(x[:, :dv], x[:, dv:]), fresh[p]))
                for p, u in enumerate(of)]
            for p, u in enumerate(of):
                o_ref[0, rows(m), 2 * p * dv:(2 * p + 2) * dv] = (
                    lanes_cat(*(e[p][r] * reads[p][r][c:] for r in range(2)))
                    + ahead[p])
            for p, u in enumerate(of):
                for r in range(2):
                    g_last = g[u][r][c - 1:c]                # (1, 1)
                    key = (k[u] if rep > 1
                           else k[u][:, r * dk:(r + 1) * dk])
                    # (1, 1) to (Dk, Dv) along the lanes first: Mosaic has
                    # no broadcast along both at once
                    state[2 * p + r] = (
                        s[p][r] * jnp.broadcast_to(jnp.exp(g_last), (1, dv))
                        + _dot(_split(key * jnp.exp(g_last - g[u][r])),
                               _both(lambda x: x[:, r * dv:(r + 1) * dv],
                                     fresh[p]), _TN))

    @pl.when(n == steps - 1)
    def _():
        so_ref[0] = state[...]


def delta_scan(state, q, k, v, g, beta):
    """A sequence, chunk by chunk, in the layout a layer has its operands:
    ``state`` ``(B, Hv, Dk, Dv)`` float32, ``Hv`` even; ``q``, ``k`` ``(B,
    T, Hk, Dk)`` normalised, by KEY head (``Hv`` a multiple of ``Hk``);
    ``v`` ``(B, T, Hv, Dv)``; ``g`` (log decay, <= 0) and ``beta`` ``(B, T,
    Hv)``; all float32, any T (padded here to whole chunks with no-op
    positions).  Returns ``(o (B, T, Hv, Dv), state after T)``:
    :func:`tpu_dist.nn.deltanet.gated_delta_chunked`'s result for ``q``,
    ``k`` repeated and everything heads-first; the state is aliased to the
    result: donate it."""
    return _call(state, q, k, v, g, beta, interpret=_use_interpret())


# jitted so that a model's layers share ONE trace and ONE Mosaic lowering
@functools.partial(jax.jit, static_argnames="interpret")
def _call(state, q, k, v, g, beta, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if hv // hk % 2 and hv != hk:
        # an odd group: a pair of value heads would straddle key heads
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        hk = hv
    rep, c = hv // hk, CHUNK
    # pairs of value heads a grid step holds, and the key heads they read
    pairs = PAIRS_A_STEP if hv % (2 * PAIRS_A_STEP) == 0 and (
        rep % (2 * PAIRS_A_STEP) == 0 or 2 * PAIRS_A_STEP % rep == 0) else 1
    key_heads = max(2 * pairs // rep, 1)
    pad = -t % c
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (a.ndim - 2))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n = (t + pad) // c
    m = math.gcd(n, CHUNKS_A_STEP)
    g = jnp.cumsum(g.reshape(b, n, c, hv), axis=2)       # within the chunk
    cols = jnp.concatenate([g.reshape(b, n * c, hv), beta], axis=-1)
    # a pair's two rows of G side by side, as the pair's matrices are
    rows = jnp.swapaxes(g, 2, 3).reshape(b, n, hv // (2 * pairs), pairs, 2 * c)
    flat = lambda a: a.reshape(b, n * c, -1)
    block = lambda width, of: pl.BlockSpec(
        (1, m * c, width), lambda i, p, s: (i, s, of(p)))
    by_step = lambda width: block(width, lambda p: p)
    by_key = block(key_heads * dk,
                   lambda p: 2 * pairs * p // (rep * key_heads))
    tiles = pl.BlockSpec((1, 2 * pairs, dk, dv), lambda i, p, s: (i, p, 0, 0))
    operands = (state, flat(q), flat(k), flat(v), cols, rows)
    out, state = pl.pallas_call(
        functools.partial(_kernel, heads=hv, rep=rep, pairs=pairs),
        grid=(b, hv // (2 * pairs), n // m),
        in_specs=[tiles, by_key, by_key, by_step(2 * pairs * dv),
                  pl.BlockSpec((1, m * c, 2 * hv), lambda i, p, s: (i, s, 0)),
                  pl.BlockSpec((1, m, 1, pairs, 2 * c),
                               lambda i, p, s: (i, s, p, 0, 0))],
        out_specs=[by_step(2 * pairs * dv), tiles],
        out_shape=[_out_struct((b, n * c, hv * dv), v.dtype, *operands),
                   _out_struct(state.shape, state.dtype, *operands)],
        scratch_shapes=[pltpu.VMEM((2 * pairs, dk, dv), jnp.float32)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
        name="delta_scan",
    )(*operands)
    return out[:, :t].reshape(b, t, hv, dv), state

"""The gated delta rule — linear-attention layers whose cache is a
fixed-size state, not a K/V pool.  Two layers over ONE recurrence:

- :class:`GatedDeltaNet` (Yang et al. 2024, "Gated Delta Networks"; the
  ``linear_attention`` layers of Qwen3-Next, HF ``modeling_qwen3_next.py``):
  the forget gate is ONE number a head a token, straight out of the input
  projection (``a``); the output norm is gated by ``silu(z)``, ``z`` a
  full-rank projection;
- :class:`KimiDeltaAttention` (Kimi Linear, arXiv:2510.26692; the KDA layers
  of ``modeling_kimi.py``): the forget gate is a VECTOR of ``Dk`` numbers a
  head a token, one a channel of the state's key axis, through a low-rank
  pair of projections; the output norm is gated by a ``sigmoid`` of a second
  low-rank pair.

Per value head a layer keeps a ``(Dk, Dv)`` float32 state ``S`` (key by
value, zero at a request's start) and reads one token as::

    S <- Diag(exp(g_t)) S;   r = v_t - S^T k_t;   S <- S + k_t (beta_t r)^T
    o_t = S^T q_t

with ``q``, ``k`` L2-normalised per head (``q`` scaled by ``Dk^-1/2``),
``beta = sigmoid(b)`` and ``g = -exp(A_log) * softplus(a + dt_bias)`` in
float32, ``g_t`` a scalar (every channel decays alike) or a vector along the
state's key axis.  ``q``, ``k``, ``v`` first pass a causal depthwise
convolution of width ``K`` and SiLU, so a layer also keeps the last ``K - 1``
inputs of that convolution, its ``conv`` tail(s).  The cost of a token does
not grow with the context.

Two forms of the same recurrence, each taking the decay's rank from its
argument's shape (``g`` one axis shorter than ``k``: a scalar a head; of
``k``'s shape: a number a channel):

- :func:`gated_delta_step`, ONE token a row (a decode step over the slot
  pool): two passes over the state, one that reads it (``S^T [k, q]`` in one
  contraction; ``o_t`` follows from it without the updated state) and one
  that reads and rewrites it.  It is the definition, the differentiable
  form and every CPU run's; a served decode step on a TPU takes
  tpu_dist.ops.delta_step instead, one Pallas call that reads a slot's
  tile once and writes it once in place (:func:`takes_step_kernel`);
- :func:`gated_delta_chunked`, a whole prompt in chunks of 64 positions
  (the WY form of HF's ``torch_chunk_gated_delta_rule``): everything inside
  a chunk is matrix products over all chunks at once, and only the state's
  carry from chunk to chunk is sequential, ``T / 64`` dependent steps where
  the token-by-token recurrence has ``T``.  The unit-lower-triangular
  inverse a chunk needs is the sum of the powers of a strictly lower (so
  nilpotent) matrix, summed by doubling: six products of 64 x 64 matrices,
  where forward substitution has 63 dependent row updates.  With a decay a
  channel the chunk's pairwise decays ``exp(G_i - G_j)`` no longer factor
  out of the products ``q_i . k_j``: :func:`_pairwise_decayed` computes them
  in sub-blocks of 16 positions.  It is the definition, the differentiable
  form, every CPU run's and the per-channel form's; a served prefill on a
  TPU whose decay is a number a head takes tpu_dist.ops.delta_scan instead,
  one Pallas call that keeps a chunk's 64 x 64 system, its products and the
  carried state in VMEM (:func:`takes_scan_kernel`).

**Positions that are nobody's** (bucket padding in a prefill, a free slot's
row in a decode step; ``valid`` false in the layer's cache entry,
nn/cache.py) are made a no-op of the recurrence, ``g = 0`` and ``beta =
0``: the state after a padded prompt is exactly the state after its last
real token, whatever the bucket, and the convolution tail is taken at the
prompt's true length.  Causal attention never reads its padding; a
recurrence runs through it, so without this a request's tokens would depend
on its bucket.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import functional as F
from . import init as I
from .module import Module
from .shortconv import advanced, causal_conv, conv_tail, valid_positions

__all__ = ["GatedDeltaNet", "KimiDeltaAttention", "gated_delta_step",
           "gated_delta_chunked", "takes_step_kernel", "takes_scan_kernel"]

CHUNK = 64
# positions a sub-block of a chunk holds where the decay is per channel
SUB = 16
# The chunked form's products are over float32 operands and the state is
# carried in float32 through up to T / 64 chunks, so the operands are not
# rounded to bfloat16 on the way in (a TPU's default): three bfloat16 passes
# (about 2^-17 relative; all six of ``HIGHEST`` timed twice as long for
# nothing the logits could show: PERF.md, PR 30).  The one-token update is
# bound by reading the state and keeps ``HIGHEST``.
_CHUNK_PRECISION = lax.Precision.HIGH
_STEP_PRECISION = lax.Precision.HIGHEST


def _l2norm(x, eps: float = 1e-6):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def gated_delta_step(state, q, k, v, g, beta):
    """One token a row.  ``state`` (B, H, Dk, Dv) float32; ``q``, ``k``
    (B, H, Dk) normalised, ``v`` (B, H, Dv); ``g`` (log decay, <= 0) (B, H),
    or (B, H, Dk) for a decay a channel; ``beta`` (B, H); all float32.
    Returns ``(o (B, H, Dv), new state)``.  A row with ``g = 0`` and
    ``beta = 0`` leaves its state as it was."""
    decay = jnp.exp(g)
    if g.ndim == k.ndim:
        # a decay a channel scales the state's ROWS, so it cannot leave the
        # contractions over them: S'^T k = S^T (decay * k), and the same of q
        sk, sq = jnp.moveaxis(
            jnp.einsum("bhkv,bhck->bhcv", state,
                       jnp.stack([k * decay, q * decay], axis=2),
                       precision=_STEP_PRECISION), 2, 0)
        delta = beta[..., None] * (v - sk)
        out = sq + jnp.sum(k * q, -1, keepdims=True) * delta
        return out, (state * decay[..., None]
                     + k[..., :, None] * delta[..., None, :])
    decay = decay[..., None]
    # one read of the state for both contractions
    sk, sq = jnp.moveaxis(
        jnp.einsum("bhkv,bhck->bhcv", state, jnp.stack([k, q], axis=2),
                   precision=_STEP_PRECISION), 2, 0)
    delta = beta[..., None] * (v - decay * sk)
    out = decay * sq + jnp.sum(k * q, -1, keepdims=True) * delta
    return out, (state * decay[..., None]
                 + k[..., :, None] * delta[..., None, :])


def _pairwise_decayed(xs, k, g, sub: int = SUB):
    """For each ``x`` of ``xs`` the chunk's matrix ``M[i, j] = sum_d x[i, d]
    k[j, d] exp(g[i, d] - g[j, d])`` over ``j <= i`` (above the diagonal:
    anything; the caller masks), where the decay is PER CHANNEL ``d``.
    ``x``, ``k``, ``g`` (..., C, Dk), ``g`` the log decay summed within the
    chunk (it falls along C).  Returns a list of (..., C, C).

    A scalar decay leaves the sum, ``exp(g_i - g_j) (x_i . k_j)``; a vector
    does not, and neither way round is open: the ``(C, C, Dk)`` tensor of
    all pairs is 2 MB a head a chunk, and the factored product ``(x_i
    exp(g_i)) . (k_j exp(-g_j))`` overflows float32 once a channel has
    decayed by e^-88 within the chunk, which the published gate reaches in
    a few steps.  So the chunk is cut into sub-blocks of ``sub`` positions
    (the published kernels' way).  BELOW the diagonal blocks the product is
    factored about the row block's first position ``r``: ``(x_i exp(g_i -
    g_r)) . (k_j exp(g_r - g_j))``, both exponents <= 0 because ``j < r <=
    i``; a factor that underflows to 0 stands for a product below 1e-38.
    ON the diagonal blocks the ``sub x sub x Dk`` pairs are computed
    directly, the exponent masked before it is taken.  Nothing here can
    overflow, whatever the decay."""
    *lead, c, dk = k.shape
    if c % sub:
        raise ValueError(f"a chunk of {c} positions is not whole sub-blocks "
                         f"of {sub}")
    nb = c // sub
    blocks = lambda a: a.reshape(*lead, nb, sub, dk)
    gb, kb = blocks(g), blocks(k)
    ref = gb[..., :1, :]                      # a block's first position
    # (nb, C): the positions in a block before row block I
    earlier = (jnp.arange(c) // sub)[None, :] < jnp.arange(nb)[:, None]
    k_side = k[..., None, :, :] * jnp.exp(jnp.where(
        earlier[..., None], ref - g[..., None, :, :], -jnp.inf))
    x_decay = jnp.exp(gb - ref)
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    pair_decay = jnp.exp(jnp.where(
        tri[..., None], gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    on_diagonal = jnp.eye(nb, dtype=k.dtype)[:, None, :, None]
    out = []
    for x in xs:
        xb = blocks(x)
        off = jnp.einsum("...isd,...ijd->...isj", xb * x_decay, k_side,
                         precision=_CHUNK_PRECISION)
        diag = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :]
                       * pair_decay, -1)                # (..., nb, sub, sub)
        out.append((off.reshape(*lead, nb, sub, nb, sub)
                    + diag[..., :, :, None, :] * on_diagonal
                    ).reshape(*lead, c, c))
    return out


def gated_delta_chunked(state, q, k, v, g, beta, chunk: int = CHUNK):
    """A sequence, chunk by chunk.  ``state`` (B, H, Dk, Dv) float32;
    ``q``, ``k`` (B, H, T, Dk) normalised, ``v`` (B, H, T, Dv), ``beta``
    (B, H, T) and ``g`` (B, H, T), or (B, H, T, Dk) for a decay a channel,
    all float32, any T (padded here to whole chunks with no-op positions).
    Returns ``(o (B, H, T, Dv), state after T)``."""
    b, h, t, dk = q.shape
    per_channel = g.ndim == q.ndim
    pad = -t % chunk
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, pad)]
                                  + [(0, 0)] * (a.ndim - 3))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk
    mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=_CHUNK_PRECISION)
    split = lambda a: a.reshape(b, h, n, chunk, *a.shape[3:])
    q, k, v, g, beta = (split(a) for a in (q, k, v, g, beta))
    g = jnp.cumsum(g, axis=3)                           # within the chunk
    # a factor of the decay against (..., Dk): a number a channel as it is,
    # a scalar over a new last axis
    chan = (lambda a: a) if per_channel else (lambda a: a[..., None])
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    if not per_channel:
        # decay from position j to position i >= j of the same chunk (masked
        # BEFORE the exponential: above the diagonal the difference is
        # positive)
        decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                                  -jnp.inf))
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    if per_channel:
        kk, qk = _pairwise_decayed((k_beta, q), k, g)
        a = jnp.where(strict, -kk, 0.0)
    else:
        a = jnp.where(strict, -mm("bhnik,bhnjk->bhnij", k_beta, k) * decay,
                      0.0)
    # (I - a)^-1 = I + a + a^2 + ... + a^(chunk-1), a being nilpotent, by
    # doubling: with S_j the sum of the first 2^j powers and p_j = a^(2^j),
    # S_(j+1) = S_j + p_j S_j and p_(j+1) = p_j p_j, ONE product p_j [p_j | S_j]
    # of 128 columns a step
    inv = jnp.eye(chunk, dtype=a.dtype) + a                  # S_1
    power = mm("bhnij,bhnjk->bhnik", a, a)                   # p_1
    for _ in range(max(chunk - 1, 1).bit_length() - 1):
        both = mm("bhnij,bhnjk->bhnik", power,
                  jnp.concatenate([power, inv], axis=-1))
        power, inv = both[..., :chunk], inv + both[..., chunk:]
    value = mm("bhnij,bhnjd->bhnid", inv, v_beta)
    k_cumdecay = mm("bhnij,bhnjd->bhnid", inv, k_beta * chan(jnp.exp(g)))
    if per_channel:
        within = jnp.where(lower, qk, 0.0)
    else:
        within = jnp.where(lower, mm("bhnik,bhnjk->bhnij", q, k) * decay,
                           0.0)
    q_decayed = q * chan(jnp.exp(g))
    g_last = g[:, :, :, -1:]
    k_carry = k * chan(jnp.exp(g_last - g))
    # what a whole chunk decays the state it starts from by: (B, H, N, Dk or
    # 1, 1) against the state's (Dk, Dv)
    chunk_decay = (jnp.swapaxes(jnp.exp(g_last), 3, 4) if per_channel
                   else jnp.exp(g_last)[..., None])

    def carry(s, xs):
        value_i, k_cum_i, within_i, q_i, k_i, decay_i = xs
        v_new = value_i - mm("bhik,bhkv->bhiv", k_cum_i, s)
        out = (mm("bhik,bhkv->bhiv", q_i, s)
               + mm("bhij,bhjv->bhiv", within_i, v_new))
        return s * decay_i + mm("bhik,bhiv->bhkv", k_i, v_new), out

    over_chunks = lambda x: jnp.moveaxis(x, 2, 0)
    state, out = lax.scan(carry, state, tuple(map(over_chunks, (
        value, k_cumdecay, within, q_decayed, k_carry, chunk_decay))))
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, n * chunk, -1)
    return out[:, :, :t], state


def _dt_bias(key, shape):
    """The inverse softplus of a log-uniform step in [1e-3, 0.1]
    (``softplus(dt_bias) = dt``): the published initialiser of a recurrent
    layer's step, so that the decay spans short and long memories."""
    dt = jnp.exp(jax.random.uniform(key, shape)
                 * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def takes_step_kernel(entry, t: int = 1) -> bool:
    """Whether a call of ``t`` positions of a recurrent layer served from
    the cache ``entry`` (None: a plain forward) computes its update with the
    Pallas kernel (tpu_dist.ops.delta_step: a slot's ``(Dk, Dv)`` tile read
    once and written once, in place) or with :func:`gated_delta_step`.
    Chosen as ``MultiheadLatentAttention.takes_slot_kernel`` chooses, by
    what the call shows: one token a row; a cache entry (a plain
    differentiable forward keeps the ``jax.numpy`` form: the kernel has no
    backward); a float32 state whose ``Dv`` fills whole lanes and whose
    ``Dk`` whole sublane tiles; and :func:`slot_kernel_wanted` (a TPU
    backend; ``attention_impl`` overrides).  Both layers answer with it
    (``layer.takes_step_kernel(entry)``); the engine asks the model, which
    asks here, which form its decode program was built on."""
    from ..ops.delta_step import delta_step_ok
    from .attention import slot_kernel_wanted
    return (t == 1 and entry is not None and delta_step_ok(entry["state"])
            and slot_kernel_wanted())


def takes_scan_kernel(entry, t: int, g) -> bool:
    """Whether a call of ``t`` positions of a recurrent layer served from
    the cache ``entry`` (None: a plain forward), its log decay ``g`` (an
    array or a shape, ``(B, t, H)`` or ``(B, t, H, Dk)``), computes its scan
    with the Pallas kernel (tpu_dist.ops.delta_scan: a chunk's 64 x 64
    system, its products and the carried state kept in VMEM) or with
    :func:`gated_delta_chunked`.  Chosen as :func:`takes_step_kernel`
    chooses, by what the call shows: more than one position; a cache entry
    (the kernel has no backward); a float32 state whose ``Dk`` and ``Dv``
    fill whole lanes; ``g`` one axis shorter than ``k`` (a decay a HEAD: a
    decay a channel does not factor out of a chunk's products and keeps the
    ``jax.numpy`` form); and :func:`slot_kernel_wanted`.  Each layer answers
    with its own ``g``'s shape (``layer.takes_scan_kernel(entry, t)``); the
    engine asks the model, which asks there, which form a bucket's prefill
    program was built on."""
    from ..ops.delta_scan import delta_scan_ok
    from .attention import slot_kernel_wanted
    return (t > 1 and entry is not None and len(g.shape) == 3
            and delta_scan_ok(entry["state"]) and slot_kernel_wanted())


def _recur(state, q, k, v, g, beta, kernel: bool = False):
    """The recurrence over a call's ``t`` positions, operands (B, t, H, .),
    ``q`` and ``k`` by KEY head (each serves ``Hv // Hk`` consecutive value
    heads): the one-token update for a decode step (``kernel``: through
    tpu_dist.ops.delta_step, :func:`takes_step_kernel`'s answer), the
    chunked scan for anything longer (``kernel``: through
    tpu_dist.ops.delta_scan, :func:`takes_scan_kernel`'s answer, which
    takes the operands as they are; the ``jax.numpy`` forms take them
    repeated and heads-first), each under its scope.  Returns ``(o (B, t,
    H, Dv), state)``."""
    if q.shape[1] > 1 and kernel:
        with jax.named_scope("scan"):
            from ..ops.delta_scan import delta_scan
            return delta_scan(state, q, k, v, g, beta)
    if v.shape[2] != q.shape[2]:
        q, k = (jnp.repeat(a, v.shape[2] // q.shape[2], axis=2)
                for a in (q, k))
    if q.shape[1] == 1:
        with jax.named_scope("state_update"):
            if kernel:
                from ..ops.delta_step import delta_step as step
            else:
                step = gated_delta_step
            out, state = step(
                state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
            return out[:, None], state
    with jax.named_scope("scan"):
        heads_first = lambda a: jnp.moveaxis(a, 2, 1)
        out, state = gated_delta_chunked(
            state, *map(heads_first, (q, k, v, g, beta)))
        return jnp.moveaxis(out, 1, 2), state               # (B, t, Hv, Dv)


def _state_flops_per_row(heads: int, k_dim: int, v_dim: int) -> int:
    """Operations ONE row's one-token update costs a layer of ``heads``
    ``(Dk, Dv)`` states: the two contractions that read the state (``S^T
    k``, ``S^T q``: 2 Dk Dv each), its decay (Dk Dv) and the rank-one update
    (2 Dk Dv)."""
    return 7 * heads * k_dim * v_dim


class GatedDeltaNet(Module):
    """The Gated DeltaNet token mixer (drop-in for a block's attention).

    Args:
        dim: model width.
        num_k_heads / num_v_heads: key heads (shared by queries) and value
            heads; each key head serves ``num_v_heads // num_k_heads``
            consecutive value heads.
        k_dim / v_dim: a key head's and a value head's size.
        conv_kernel: width of the causal depthwise convolution over
            ``[q | k | v]``.
        eps: of the gated output norm.

    Parameters: ``qkvz_weight`` ``(dim, 2 Hk Dk + 2 Hv Dv)`` split ``[q | k |
    v | z]``, ``ba_weight`` ``(dim, 2 Hv)`` split ``[b | a]``,
    ``conv_weight`` ``(Hk Dk * 2 + Hv Dv, K)`` (tap ``K - 1`` is the current
    position's), ``A_log``, ``dt_bias`` ``(Hv,)``, ``norm_weight`` ``(Dv,)``
    and ``out_weight`` ``(Hv Dv, dim)``; no biases.  ``A_log`` is the log of
    U(0, 16) and ``dt_bias`` the inverse softplus of a log-uniform step in
    [1e-3, 0.1], so at initialisation ``exp(g)`` spans short and long
    memories.

    Served through a slot cache the layer keeps per slot
    (:meth:`init_cache`) ``state`` and ``conv``, both replaced entire at
    every call; called without a cache (a plain forward) it starts every
    sequence from the zero state.
    """

    def __init__(self, dim: int, num_k_heads: int, num_v_heads: int,
                 k_dim: int, v_dim: int, conv_kernel: int = 4,
                 eps: float = 1e-6):
        super().__init__()
        if num_v_heads % num_k_heads:
            raise ValueError(f"num_v_heads {num_v_heads} not divisible by "
                             f"num_k_heads {num_k_heads}")
        self.dim = dim
        self.num_k_heads, self.num_v_heads = num_k_heads, num_v_heads
        self.k_dim, self.v_dim = k_dim, v_dim
        self.key_dim, self.value_dim = num_k_heads * k_dim, num_v_heads * v_dim
        self.conv_dim = 2 * self.key_dim + self.value_dim
        self.conv_kernel = conv_kernel
        self.eps = eps

    #: a layer of whole state reads no resident position
    attend_flops_per_position = 0
    takes_step_kernel = staticmethod(takes_step_kernel)

    def takes_scan_kernel(self, entry, t: int) -> bool:
        """:func:`takes_scan_kernel` of a call of ``t`` positions: this
        layer's decay is a number a head."""
        return takes_scan_kernel(entry, t, jax.ShapeDtypeStruct(
            (1, t, self.num_v_heads), jnp.float32))

    @property
    def state_flops_per_row(self) -> int:
        """Operations one row's one-token update costs this layer
        (:func:`_state_flops_per_row`)."""
        return _state_flops_per_row(self.num_v_heads, self.k_dim, self.v_dim)

    def create_params(self, key):
        ks = jax.random.split(key, 6)
        hv = self.num_v_heads
        return {
            "qkvz_weight": I.torch_default_uniform(
                ks[0], (self.dim, 2 * self.key_dim + 2 * self.value_dim),
                self.dim),
            "ba_weight": I.torch_default_uniform(
                ks[1], (self.dim, 2 * hv), self.dim),
            "conv_weight": I.torch_default_uniform(
                ks[2], (self.conv_dim, self.conv_kernel), self.conv_kernel),
            "A_log": jnp.log(jax.random.uniform(ks[3], (hv,), minval=1e-3,
                                                maxval=16.0)),
            "dt_bias": _dt_bias(ks[4], (hv,)),
            "norm_weight": jnp.ones((self.v_dim,)),
            "out_weight": I.torch_default_uniform(
                ks[5], (self.value_dim, self.dim), self.value_dim),
        }

    def init_cache(self, batch: int, max_len: int = 0, dtype=jnp.float32):
        """What this layer keeps per slot (one entry of a nn/cache.py
        tree): ``state`` ``(B, Hv, Dk, Dv)``, float32 whatever ``dtype``
        (the recurrence accumulates a whole context into it), and ``conv``
        ``(B, (K - 1) * C)`` in ``dtype``, the convolution's last ``K - 1``
        inputs, oldest first, flattened so that no axis of 3 is padded to
        a tile.  Neither has a time axis: ``max_len`` does not size them."""
        return {"state": jnp.zeros((batch, self.num_v_heads, self.k_dim,
                                    self.v_dim), jnp.float32),
                "conv": jnp.zeros((batch, (self.conv_kernel - 1)
                                   * self.conv_dim), dtype)}

    def forward(self, x):
        from .module import _ctx
        ctx = _ctx()
        p = ctx.get_params(self._path)
        st = (ctx.get_state(self._path)
              if ctx.state is not None and self._path in ctx.state else None)
        b, t, _ = x.shape
        taps = self.conv_kernel - 1
        qkvz = F.linear(x, p["qkvz_weight"])
        mixed, z = qkvz[..., :self.conv_dim], qkvz[..., self.conv_dim:]
        ba = F.linear(x, p["ba_weight"]).astype(jnp.float32)
        valid = valid_positions(st, b, t)

        with jax.named_scope("conv"):
            tail = conv_tail(st, "conv", jax.ShapeDtypeStruct(
                (b, taps, self.conv_dim), mixed.dtype))
            mixed, new_tail = causal_conv(mixed, tail, p["conv_weight"],
                                          valid)

        hk, hv = self.num_k_heads, self.num_v_heads
        f32 = lambda a: a.astype(jnp.float32)
        q, k, v = jnp.split(mixed, [self.key_dim, 2 * self.key_dim], axis=-1)
        q = _l2norm(f32(q.reshape(b, t, hk, self.k_dim))) * self.k_dim ** -0.5
        k = _l2norm(f32(k.reshape(b, t, hk, self.k_dim)))
        v = f32(v.reshape(b, t, hv, self.v_dim))
        # nobody's positions: beta = 0 and g = 0, the recurrence's no-op
        beta = jnp.where(valid[..., None], jax.nn.sigmoid(ba[..., :hv]), 0.0)
        g = jnp.where(valid[..., None],
                      -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(
                          ba[..., hv:] + f32(p["dt_bias"])), 0.0)
        state = (jnp.zeros((b, hv, self.k_dim, self.v_dim), jnp.float32)
                 if st is None else st["state"])
        out, state = _recur(state, q, k, v, g, beta,
                            kernel=(takes_step_kernel(st, t)
                                    or takes_scan_kernel(st, t, g)))
        if st is not None:
            ctx.put_state(self._path, advanced(
                st, t, state=state,
                conv=new_tail.reshape(b, -1).astype(st["conv"].dtype)))
        with jax.named_scope("gate_norm"):
            z = z.reshape(b, t, hv, self.v_dim)
            y = (F.rms_norm(out, f32(p["norm_weight"]), self.eps)
                 * jax.nn.silu(f32(z))).astype(x.dtype)
        return F.linear(y.reshape(b, t, self.value_dim), p["out_weight"])

    def __repr__(self):
        return (f"GatedDeltaNet({self.dim}, k_heads={self.num_k_heads}, "
                f"v_heads={self.num_v_heads}, k_dim={self.k_dim}, "
                f"v_dim={self.v_dim})")


class KimiDeltaAttention(Module):
    """The Kimi Delta Attention token mixer (KDA; drop-in for a block's
    attention): the recurrence above with a decay a CHANNEL of the state's
    key axis.

    Args:
        dim: model width.
        num_heads: heads; queries, keys and values all have this many.
        head_dim: a head's size, keys and values alike (``Dk = Dv``); also
            the rank of the two low-rank gates, as published.
        conv_kernel: width of the three causal depthwise convolutions.
        eps: of the gated output norm.

    Parameters (no biases): ``q_weight``, ``k_weight``, ``v_weight`` ``(dim,
    H D)``, each through a convolution of its OWN, ``q_conv_weight``,
    ``k_conv_weight``, ``v_conv_weight`` ``(H D, K)`` (tap ``K - 1`` is the
    current position's); the forget gate ``g = -exp(A_log) softplus((x
    f_a_weight) f_b_weight + dt_bias)`` with ``f_a_weight`` ``(dim, D)``,
    ``f_b_weight`` ``(D, H D)``, ``A_log`` ``(H,)`` and ``dt_bias`` ``(H
    D,)``; ``b_weight`` ``(dim, H)`` for ``beta``; the output gate
    ``sigmoid((x g_a_weight) g_b_weight)`` with ``g_a_weight`` ``(dim, D)``,
    ``g_b_weight`` ``(D, H D)``; ``norm_weight`` ``(D,)`` and ``out_weight``
    ``(H D, dim)``.  ``A_log`` is the log of U(1, 16) a head and ``dt_bias``
    the inverse softplus of a log-uniform step in [1e-3, 0.1] a channel (the
    published code's initialisers), so at initialisation the channels of
    one head span short and long memories.

    Served through a slot cache the layer keeps per slot
    (:meth:`init_cache`) ``state`` and three convolution tails, all replaced
    entire at every call; called without a cache (a plain forward) it
    starts every sequence from the zero state.
    """

    _CONVS = ("q", "k", "v")

    def __init__(self, dim: int, num_heads: int, head_dim: int,
                 conv_kernel: int = 4, eps: float = 1e-5):
        super().__init__()
        self.dim = dim
        self.num_heads, self.head_dim = num_heads, head_dim
        self.proj_dim = num_heads * head_dim
        self.conv_kernel = conv_kernel
        self.eps = eps

    #: a layer of whole state reads no resident position
    attend_flops_per_position = 0
    takes_step_kernel = staticmethod(takes_step_kernel)

    def takes_scan_kernel(self, entry, t: int) -> bool:
        """:func:`takes_scan_kernel` of a call of ``t`` positions: this
        layer's decay is a number a CHANNEL, so never."""
        return takes_scan_kernel(entry, t, jax.ShapeDtypeStruct(
            (1, t, self.num_heads, self.head_dim), jnp.float32))

    @property
    def state_flops_per_row(self) -> int:
        """Operations one row's one-token update costs this layer
        (:func:`_state_flops_per_row`)."""
        return _state_flops_per_row(self.num_heads, self.head_dim,
                                    self.head_dim)

    def create_params(self, key):
        ks = jax.random.split(key, 14)
        h, d, width = self.num_heads, self.head_dim, self.proj_dim
        lin = lambda k, fan_in, fan_out: I.torch_default_uniform(
            k, (fan_in, fan_out), fan_in)
        conv = lambda k: I.torch_default_uniform(
            k, (width, self.conv_kernel), self.conv_kernel)
        return {
            "q_weight": lin(ks[0], self.dim, width),
            "k_weight": lin(ks[1], self.dim, width),
            "v_weight": lin(ks[2], self.dim, width),
            "q_conv_weight": conv(ks[3]),
            "k_conv_weight": conv(ks[4]),
            "v_conv_weight": conv(ks[5]),
            "f_a_weight": lin(ks[6], self.dim, d),
            "f_b_weight": lin(ks[7], d, width),
            "b_weight": lin(ks[8], self.dim, h),
            "g_a_weight": lin(ks[9], self.dim, d),
            "g_b_weight": lin(ks[10], d, width),
            "A_log": jnp.log(jax.random.uniform(ks[12], (h,), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": _dt_bias(ks[11], (width,)),
            "norm_weight": jnp.ones((d,)),
            "out_weight": lin(ks[13], width, self.dim),
        }

    def init_cache(self, batch: int, max_len: int = 0, dtype=jnp.float32):
        """What this layer keeps per slot (one entry of a nn/cache.py
        tree): ``state`` ``(B, H, D, D)``, float32 whatever ``dtype``, and
        ``conv_q``, ``conv_k``, ``conv_v`` ``(B, (K - 1) * H D)`` in
        ``dtype``, each convolution's last ``K - 1`` inputs, oldest first,
        flattened as :meth:`GatedDeltaNet.init_cache` flattens its one.
        None has a time axis: ``max_len`` does not size them."""
        tail = (batch, (self.conv_kernel - 1) * self.proj_dim)
        return {"state": jnp.zeros((batch, self.num_heads, self.head_dim,
                                    self.head_dim), jnp.float32),
                **{f"conv_{c}": jnp.zeros(tail, dtype) for c in self._CONVS}}

    def forward(self, x):
        from .module import _ctx
        ctx = _ctx()
        p = ctx.get_params(self._path)
        st = (ctx.get_state(self._path)
              if ctx.state is not None and self._path in ctx.state else None)
        b, t, _ = x.shape
        h, d, taps = self.num_heads, self.head_dim, self.conv_kernel - 1
        valid = valid_positions(st, b, t)
        f32 = lambda a: a.astype(jnp.float32)

        qkv, tails = [], {}
        for c in self._CONVS:
            mixed = F.linear(x, p[f"{c}_weight"])
            with jax.named_scope("conv"):
                tail = conv_tail(st, f"conv_{c}", jax.ShapeDtypeStruct(
                    (b, taps, self.proj_dim), mixed.dtype))
                mixed, tails[f"conv_{c}"] = causal_conv(
                    mixed, tail, p[f"{c}_conv_weight"], valid)
            qkv.append(f32(mixed.reshape(b, t, h, d)))
        q, k, v = qkv
        q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
        with jax.named_scope("gate"):
            low_rank = lambda a: F.linear(F.linear(x, p[f"{a}_a_weight"]),
                                          p[f"{a}_b_weight"])
            # nobody's positions: beta = 0 and g = 0, the recurrence's no-op
            g = jnp.where(
                valid[..., None, None],
                -jnp.exp(f32(p["A_log"]))[:, None] * jax.nn.softplus(
                    f32(low_rank("f")).reshape(b, t, h, d)
                    + f32(p["dt_bias"]).reshape(h, d)), 0.0)
            beta = jnp.where(valid[..., None], jax.nn.sigmoid(
                f32(F.linear(x, p["b_weight"]))), 0.0)
            z = low_rank("g").reshape(b, t, h, d)
        state = (jnp.zeros((b, h, d, d), jnp.float32)
                 if st is None else st["state"])
        out, state = _recur(state, q, k, v, g, beta,
                            kernel=(takes_step_kernel(st, t)
                                    or takes_scan_kernel(st, t, g)))
        if st is not None:
            ctx.put_state(self._path, advanced(
                st, t, state=state,
                **{name: tail.reshape(b, -1).astype(st[name].dtype)
                   for name, tail in tails.items()}))
        with jax.named_scope("gate_norm"):
            y = (F.rms_norm(out, f32(p["norm_weight"]), self.eps)
                 * jax.nn.sigmoid(f32(z))).astype(x.dtype)
        return F.linear(y.reshape(b, t, self.proj_dim), p["out_weight"])

    def __repr__(self):
        return (f"KimiDeltaAttention({self.dim}, heads={self.num_heads}, "
                f"head_dim={self.head_dim})")

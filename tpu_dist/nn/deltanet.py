"""Gated DeltaNet — a linear-attention layer whose cache is a fixed-size
state, not a K/V pool (Yang et al. 2024, "Gated Delta Networks"; the
``linear_attention`` layers of Qwen3-Next, HF ``modeling_qwen3_next.py``).

Per value head the layer keeps a ``(Dk, Dv)`` float32 state ``S`` (key by
value, zero at a request's start) and reads one token as::

    S <- exp(g_t) S;   r = v_t - S^T k_t;   S <- S + k_t (beta_t r)^T
    o_t = S^T q_t

with ``q``, ``k`` L2-normalised per head (``q`` scaled by ``Dk^-1/2``),
``beta = sigmoid(b)`` and ``g = -exp(A_log) * softplus(a + dt_bias)`` in
float32.  ``q``, ``k``, ``v`` first pass a causal depthwise convolution of
width ``K`` and SiLU, so the layer also keeps the last ``K - 1`` inputs of
that convolution, its ``conv`` tail.  The cost of a token does not grow with
the context: the first layer kind here of which that is true.

Two forms of the same recurrence:

- :func:`gated_delta_step`, ONE token a row (a decode step over the slot
  pool): two passes over the state, one that reads it (``S^T [k, q]`` in one
  contraction; ``o_t`` follows from it without the updated state) and one
  that reads and rewrites it;
- :func:`gated_delta_chunked`, a whole prompt in chunks of 64 positions
  (the WY form of HF's ``torch_chunk_gated_delta_rule``): everything inside
  a chunk is matrix products over all chunks at once, and only the state's
  carry from chunk to chunk is sequential, ``T / 64`` dependent steps where
  the token-by-token recurrence has ``T``.  The unit-lower-triangular
  inverse a chunk needs is the sum of the powers of a strictly lower (so
  nilpotent) matrix, summed by doubling: six products of 64 x 64 matrices,
  where forward substitution has 63 dependent row updates.

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

__all__ = ["GatedDeltaNet", "gated_delta_step", "gated_delta_chunked"]

CHUNK = 64
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
    (B, H, Dk) normalised, ``v`` (B, H, Dv); ``g`` (log decay, <= 0) and
    ``beta`` (B, H); all float32.  Returns ``(o (B, H, Dv), new state)``.
    A row with ``g = 0`` and ``beta = 0`` leaves its state as it was."""
    decay = jnp.exp(g)[..., None]
    # one read of the state for both contractions
    sk, sq = jnp.moveaxis(
        jnp.einsum("bhkv,bhck->bhcv", state, jnp.stack([k, q], axis=2),
                   precision=_STEP_PRECISION), 2, 0)
    delta = beta[..., None] * (v - decay * sk)
    out = decay * sq + jnp.sum(k * q, -1, keepdims=True) * delta
    return out, (state * decay[..., None]
                 + k[..., :, None] * delta[..., None, :])


def gated_delta_chunked(state, q, k, v, g, beta, chunk: int = CHUNK):
    """A sequence, chunk by chunk.  ``state`` (B, H, Dk, Dv) float32;
    ``q``, ``k`` (B, H, T, Dk) normalised, ``v`` (B, H, T, Dv), ``g`` and
    ``beta`` (B, H, T), all float32, any T (padded here to whole chunks
    with no-op positions).  Returns ``(o (B, H, T, Dv), state after T)``."""
    b, h, t, dk = q.shape
    pad = -t % chunk
    if pad:
        widen = lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, pad)]
                                  + [(0, 0)] * (a.ndim - 3))
        q, k, v, g, beta = (widen(a) for a in (q, k, v, g, beta))
    n = (t + pad) // chunk
    mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=_CHUNK_PRECISION)
    split = lambda a: a.reshape(b, h, n, chunk, *a.shape[3:])
    q, k, v, g, beta = (split(a) for a in (q, k, v, g, beta))
    g = jnp.cumsum(g, axis=-1)                          # within the chunk
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    # decay from position j to position i >= j of the same chunk (masked
    # BEFORE the exponential: above the diagonal the difference is positive)
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
    k_beta, v_beta = k * beta[..., None], v * beta[..., None]
    a = jnp.where(strict, -mm("bhnik,bhnjk->bhnij", k_beta, k) * decay, 0.0)
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
    k_cumdecay = mm("bhnij,bhnjd->bhnid", inv,
                    k_beta * jnp.exp(g)[..., None])
    within = jnp.where(lower, mm("bhnik,bhnjk->bhnij", q, k) * decay, 0.0)
    q_decayed = q * jnp.exp(g)[..., None]
    g_last = g[..., -1:]
    k_carry = k * jnp.exp(g_last - g)[..., None]
    chunk_decay = jnp.exp(g_last)[..., None]            # (B, H, N, 1, 1)

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

    def create_params(self, key):
        ks = jax.random.split(key, 6)
        hv = self.num_v_heads
        dt = jnp.exp(jax.random.uniform(ks[4], (hv,))
                     * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
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
            # softplus(dt_bias) = dt
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
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
        valid = None if st is None else st.get("valid")
        if valid is None:
            valid = jnp.ones((b, t), bool)
        valid = jnp.broadcast_to(valid, (b, t))

        with jax.named_scope("conv"):
            tail = (jnp.zeros((b, taps, self.conv_dim), mixed.dtype)
                    if st is None else
                    st["conv"].reshape(b, taps, self.conv_dim)
                    .astype(mixed.dtype))
            window = jnp.concatenate([tail, mixed], axis=1)  # (B, K-1+t, C)
            w = p["conv_weight"].astype(mixed.dtype)
            mixed = jax.nn.silu(sum(
                window[:, j:j + t] * w[:, j] for j in range(taps + 1)))
            # the tail after the LAST REAL position: rows [n, n + K - 1) of
            # the window, n the call's count of real positions (they lead)
            n_real = valid.sum(-1).astype(jnp.int32)
            new_tail = jax.vmap(lambda win, n: lax.dynamic_slice_in_dim(
                win, n, taps, axis=0))(window, n_real)

        hk, hv = self.num_k_heads, self.num_v_heads
        f32 = lambda a: a.astype(jnp.float32)
        q, k, v = jnp.split(mixed, [self.key_dim, 2 * self.key_dim], axis=-1)
        q = _l2norm(f32(q.reshape(b, t, hk, self.k_dim))) * self.k_dim ** -0.5
        k = _l2norm(f32(k.reshape(b, t, hk, self.k_dim)))
        q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
        v = f32(v.reshape(b, t, hv, self.v_dim))
        # nobody's positions: beta = 0 and g = 0, the recurrence's no-op
        beta = jnp.where(valid[..., None], jax.nn.sigmoid(ba[..., :hv]), 0.0)
        g = jnp.where(valid[..., None],
                      -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(
                          ba[..., hv:] + f32(p["dt_bias"])), 0.0)
        state = (jnp.zeros((b, hv, self.k_dim, self.v_dim), jnp.float32)
                 if st is None else st["state"])
        if t == 1:
            with jax.named_scope("state_update"):
                out, state = gated_delta_step(
                    state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                out = out[:, None]
        else:
            with jax.named_scope("scan"):
                heads_first = lambda a: jnp.moveaxis(a, 2, 1)
                out, state = gated_delta_chunked(
                    state, *map(heads_first, (q, k, v, g, beta)))
                out = jnp.moveaxis(out, 1, 2)               # (B, t, Hv, Dv)
        if st is not None:
            ctx.put_state(self._path, dict(
                st, state=state, index=jnp.asarray(st["index"]) + t,
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

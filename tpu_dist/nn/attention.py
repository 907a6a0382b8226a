"""Attention layers — the sequence-model substrate.

The reference has no attention (workloads are 28²/32² image classifiers,
SURVEY.md §5 long-context row: absent).  tpu_dist treats long-context as
first-class: these layers run dense single-device attention by default and
switch to **sequence-parallel** execution (ring attention or Ulysses
all-to-all, tpu_dist.parallel.ring_attention) when given a mesh axis, so the
same model scales from one chip to a pod slice with a constructor argument.

Functional core: :func:`scaled_dot_product_attention` (flash-style math is
XLA's job on TPU — it fuses and tiles the softmax; the explicitly blocked
variants live in the parallel package where the blocking crosses devices).
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import functional as F
from .module import Module
from . import init as I

__all__ = ["scaled_dot_product_attention", "MultiheadSelfAttention",
           "attention_impl", "rotary_embed", "yarn_inv_freq", "yarn_mscale"]

_IMPL_OVERRIDE: list = []

# auto-dispatch crossover: below this sequence length the XLA-fused dense
# path beats the Pallas kernel (tile padding to the 128-lane grid plus
# kernel launch overhead dominate when the score matrix is small).
# Leads from the shared v5e chip of the rounds before PR 1 (not measured
# on this machine): ViT-B at T=197 trained 1.54x faster dense; GPT-2-small
# at T=2048 trained 1.63x faster fwd+bwd with flash.
_FLASH_MIN_SEQ = 1024


@contextlib.contextmanager
def attention_impl(impl: str):
    """Trace-scoped default for :func:`scaled_dot_product_attention`'s
    ``impl`` — overrides the auto choice for every attention call traced
    inside the block (explicit per-call ``impl=`` still wins).  Used by
    ``make_gspmd_train_step`` to force ``"dense"``: a Pallas custom call
    can't be cut by XLA's SPMD partitioner, so under GSPMD-sharded jit the
    flash kernel must not be auto-dispatched (inside ``shard_map`` — the
    DDP and ring-attention paths — per-device flash is fine and used)."""
    _IMPL_OVERRIDE.append(impl)
    try:
        yield
    finally:
        _IMPL_OVERRIDE.pop()


def slot_kernel_wanted() -> bool:
    """Whether a slot-decode step should take its Pallas kernel where the
    pool suits one: on a TPU backend (interpreted, a kernel would make
    every served token cost seconds), with the trace-scoped
    :func:`attention_impl` (``"flash"`` / ``"dense"``) as the override."""
    impl = (_IMPL_OVERRIDE[-1] if _IMPL_OVERRIDE
            else "flash" if jax.default_backend() == "tpu" else "dense")
    return impl == "flash"


def scaled_dot_product_attention(q, k, v, causal: bool = False,
                                 mask: Optional[jax.Array] = None,
                                 impl: Optional[str] = None):
    """Attention.  ``q,k,v``: (..., T, H, D) → (..., T, H, D).  ``k`` and
    ``v`` may hold fewer heads than ``q`` (grouped queries: K/V head ``j``
    serves query heads ``[j * G, (j + 1) * G)``); they are then repeated
    for the computation, which is the dense one.

    ``mask``: broadcastable to (..., H, Tq, Tk), True = keep.

    ``impl``: ``"dense"`` materializes the (Tq, Tk) scores (supports
    arbitrary masks); ``"flash"`` runs the O(T)-memory Pallas kernel
    (tpu_dist.ops.flash_attention; causal/no-mask only).  Default (None /
    ``"auto"``): flash on TPU backends when no arbitrary mask is given
    AND the sequence is at least ``_FLASH_MIN_SEQ`` (short sequences are
    faster through XLA's fused dense path — see the crossover note at the
    constant); dense elsewhere (the kernel runs interpreted off-TPU —
    correct but slower than XLA's fused dense path).
    """
    if k.shape[-2] != q.shape[-2]:
        group = q.shape[-2] // k.shape[-2]
        k, v = (jnp.repeat(a, group, axis=-2) for a in (k, v))
    if impl in (None, "auto"):
        if _IMPL_OVERRIDE:
            impl = _IMPL_OVERRIDE[-1]
        else:
            flash_ok = (mask is None and jax.default_backend() == "tpu"
                        and max(q.shape[-3], k.shape[-3]) >= _FLASH_MIN_SEQ
                        and q.shape[:-3] == k.shape[:-3] == v.shape[:-3]
                        and k.shape == v.shape)  # no broadcast-KV kernel path
            impl = "flash" if flash_ok else "dense"
    if impl == "flash":
        if mask is not None:
            raise ValueError("impl='flash' supports causal masking only; "
                             "pass impl='dense' for arbitrary masks")
        from ..ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal)
    if impl != "dense":
        raise ValueError(f"Unknown attention impl {impl!r}")
    d = q.shape[-1]
    # (..., H, Tq, Tk)
    scores = jnp.einsum("...qhd,...khd->...hqk", q, k) / math.sqrt(d)
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        qpos = jnp.arange(tq)[:, None]
        kpos = jnp.arange(tk)[None, :]
        scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", w, v)


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's blended rotary frequencies (Peng et al., arXiv:2309.00071),
    ``dim // 2`` float32 values for :func:`rotary_embed`'s ``inv_freq``, as
    the published DeepSeek-V3 code computes them
    (``DeepseekV3YarnRotaryEmbedding``): pair ``i`` keeps its own frequency
    ``theta^(-2i/dim)`` where it turns more than ``beta_fast`` times within
    the original context, takes ``1/factor`` of it (interpolated
    positions) where it turns fewer than ``beta_slow`` times, and a linear
    blend between: ``low`` / ``high`` are the floor / ceiling of the pair
    indices at which it turns exactly ``beta_fast`` / ``beta_slow`` times,
    and the ramp over ``[low, high]`` widens a range whose ends coincide
    by 0.001, as ``yarn_linear_ramp_mask`` does.  Host arithmetic on the
    configuration's scalars: a constant of the program."""
    def turns_at(rotations):
        return (dim * math.log(original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pair = np.arange(dim // 2, dtype=np.float32)
    own = 1.0 / theta ** (2.0 * pair / dim)
    keep = 1.0 - np.clip((pair - low) / (high - low), 0.0, 1.0)
    return (own / factor * (1.0 - keep) + own * keep).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times: ``0.1 * mscale * ln(factor) + 1`` (1 for no stretch)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embed(x, positions, theta: float = 10000.0, rotary_dim=None,
                 inv_freq=None):
    """Rotate ``x`` (..., T, H, D) by per-position angles — RoPE (Su et al.,
    arXiv:2104.09864), rotate-half convention.  ``positions``: (T,) int
    absolute positions; attention scores then depend only on relative
    distance, so no learned position table is needed and contexts
    extrapolate.  Angles computed in f32, result cast back to x.dtype.
    ``rotary_dim`` < D rotates the first ``rotary_dim`` dims of each head
    as a head of that size and leaves the others untouched (a partial
    rotary factor).  ``inv_freq`` (D // 2,) gives the pairs' frequencies
    in place of ``theta``'s geometric ones (:func:`yarn_inv_freq`)."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [rotary_embed(x[..., :rotary_dim], positions, theta,
                          inv_freq=inv_freq),
             x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    half = d // 2
    freqs = (theta ** (-jnp.arange(0, half, dtype=jnp.float32) * 2.0 / d)
             if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    # positions may be (T,) — shared across the batch — or carry leading
    # batch dims, e.g. (B, T) during per-slot continuous-batching decode
    # where every cache slot sits at its own position
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, half)
    # (..., T, 1, half) broadcasts against (..., T, H, half) for ANY number
    # of leading batch dims (including none)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _write_columns(pool, new, index):
    """Per-slot column write: ``new`` (B, ..., t) lands in columns
    ``[index[b], index[b] + t)`` of each row b of ``pool`` (B, ..., Tmax),
    time last; a column past ``Tmax`` is dropped.

    Written as a select over the pool, not as a scatter: XLA fuses the
    select into the attention's q.K / P.V reduction as one multi-output
    pass that reads the pool once and rewrites the donated buffer in
    place, where a native scatter wants the pool in a layout of its own
    (a 2.56x padded whole-pool copy there and back, each step).  On the
    chip (PERF.md, PR 24) this beat the ``vmap``-ed
    ``dynamic_update_slice``, which XLA expands into a per-slot loop of
    partial-tile writes.  ``t`` selects are unrolled: t is 1 in slot
    decode and small in a multi-token append; whole prompts take the
    scalar-index branch of :meth:`MultiheadSelfAttention._decode`."""
    kpos = jnp.arange(pool.shape[-1])
    start = index.reshape((-1,) + (1,) * (pool.ndim - 1))
    for j in range(new.shape[-1]):
        pool = jnp.where(kpos == start + j, new[..., j:j + 1], pool)
    return pool


class MultiheadSelfAttention(Module):
    """Multi-head self-attention with fused QKV projection.

    ``sequence_axis``: when set (e.g. ``'seq'``) and traced inside
    ``shard_map`` over that mesh axis, the layer computes sequence-parallel
    attention — ``mode='ring'`` rotates KV blocks around the ring
    (ring attention), ``mode='ulysses'`` redistributes heads via all-to-all.
    Results equal the dense computation (tested in tests/test_ring_attention.py).

    ``num_kv_heads`` < ``num_heads`` shares each K/V head among a group of
    query heads (the cache then holds ``num_kv_heads`` heads), ``head_dim``
    sets a head's size apart from ``embed_dim // num_heads``,
    ``rotary_dim`` rotates only the first dims of each head, ``qk_norm=
    "head"`` normalises q and k over EACH head's dims with one zero-centred
    weight of ``head_dim`` shared by the heads (``True``: over the whole
    projection, OLMoE's), and ``gated`` multiplies the attention's output
    by the sigmoid of a second, query-sized projection of the input before
    the output projection (Qwen3-Next's full-attention layer).
    ``key_multiplier`` scales the keys' projection, a constant of the
    program (Falcon-H1's; 1 is no operation).
    """

    def __init__(self, embed_dim: int, num_heads: int, bias: bool = True,
                 causal: bool = False, sequence_axis: Optional[str] = None,
                 mode: str = "ring", attn_impl: Optional[str] = None,
                 rope: bool = False, rope_theta: float = 10000.0,
                 qk_norm=False, qk_norm_eps: float = 1e-6,
                 num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None,
                 rotary_dim: Optional[int] = None, gated: bool = False,
                 key_multiplier: float = 1.0):
        super().__init__()
        if head_dim is None and embed_dim % num_heads:
            raise ValueError(f"embed_dim {embed_dim} not divisible by "
                             f"num_heads {num_heads}")
        head_dim = head_dim or embed_dim // num_heads
        num_kv_heads = num_kv_heads or num_heads
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not divisible by "
                             f"num_kv_heads {num_kv_heads}")
        if mode not in ("ring", "ulysses"):
            raise ValueError(f"Unknown sequence-parallel mode {mode!r}")
        if rope and (rotary_dim or head_dim) % 2:
            raise ValueError(f"rotary embeddings need an even head_dim, "
                             f"got {rotary_dim or head_dim}")
        if qk_norm not in (False, True, "head"):
            raise ValueError(f"qk_norm must be False, True or 'head', got "
                             f"{qk_norm!r}")
        if sequence_axis is not None and num_kv_heads != num_heads:
            raise ValueError("sequence-parallel attention has no "
                             "grouped-query form")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.q_dim = num_heads * head_dim
        self.kv_dim = num_kv_heads * head_dim
        self.rotary_dim = rotary_dim
        self.gated = gated
        self.key_multiplier = float(key_multiplier)
        self.bias = bias
        self.causal = causal
        self.sequence_axis = sequence_axis
        self.mode = mode
        self.attn_impl = attn_impl  # None=auto | "dense" | "flash"
        self.rope = rope
        self.rope_theta = rope_theta
        # True, OLMoE's QK-norm: an RMSNorm with its own weight over the
        # WHOLE q and k projections (all heads together), before the head
        # split and before rope.  "head", Qwen3-Next's: over each head's
        # dims after the split, zero-centred (x_hat * (1 + w))
        self.qk_norm = qk_norm
        self.qk_norm_eps = qk_norm_eps

    @property
    def attend_flops_per_position(self) -> int:
        """Operations one resident position costs one new query of this
        layer: ``q . k`` and ``p . v`` over ``head_dim``, every query
        head."""
        return 2 * self.num_heads * 2 * self.head_dim

    def create_params(self, key):
        k1, k2 = jax.random.split(key)
        # one fused matrix, split [q | gate | k | v] (gate only when gated)
        fused = self.q_dim * (2 if self.gated else 1) + 2 * self.kv_dim
        p = {"qkv_weight": I.torch_default_uniform(
                 k1, (self.embed_dim, fused), self.embed_dim),
             "out_weight": I.torch_default_uniform(
                 k2, (self.q_dim, self.embed_dim), self.q_dim)}
        if self.bias:
            p["qkv_bias"] = jnp.zeros((fused,))
            p["out_bias"] = jnp.zeros((self.embed_dim,))
        if self.qk_norm == "head":
            p["q_norm_weight"] = jnp.zeros((self.head_dim,))
            p["k_norm_weight"] = jnp.zeros((self.head_dim,))
        elif self.qk_norm:
            p["q_norm_weight"] = jnp.ones((self.q_dim,))
            p["k_norm_weight"] = jnp.ones((self.kv_dim,))
        return p

    def _qkv_proj(self, p, x):
        """The fused qkv projection — overridden by the int8 inference
        subclass (nn.quant.QuantMultiheadSelfAttention), which hoists its
        per-channel scale to the (tiny) output instead of dequantizing the
        (huge) weight."""
        return F.linear(x, p["qkv_weight"], p.get("qkv_bias"))

    def _out_proj(self, p, out):
        return F.linear(out, p["out_weight"], p.get("out_bias"))

    def forward(self, x):
        from .module import _ctx
        ctx = _ctx()
        p = ctx.get_params(self._path)
        b, t, _ = x.shape
        gate = None
        if self.gated or self.kv_dim != self.q_dim:
            # [q | gate | k | v], the gate only when gated
            sizes = ([self.q_dim] * (2 if self.gated else 1)
                     + [self.kv_dim, self.kv_dim])
            q, *gate, k, v = jnp.split(self._qkv_proj(p, x),
                                       np.cumsum(sizes)[:-1], axis=-1)
            gate = gate[0] if gate else None
        else:
            qkv = self._qkv_proj(p, x).reshape(b, t, 3, self.embed_dim)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        k = F.scaled(k, self.key_multiplier)
        if self.qk_norm is True:
            q = F.rms_norm(q, p["q_norm_weight"], self.qk_norm_eps)
            k = F.rms_norm(k, p["k_norm_weight"], self.qk_norm_eps)
        q = q.reshape(b, t, self.num_heads, self.head_dim)
        k, v = (a.reshape(b, t, self.num_kv_heads, self.head_dim)
                for a in (k, v))
        if self.qk_norm == "head":
            q = F.rms_norm(q, 1.0 + p["q_norm_weight"], self.qk_norm_eps)
            k = F.rms_norm(k, 1.0 + p["k_norm_weight"], self.qk_norm_eps)
        if self.rope:
            # absolute positions of THESE tokens: the cache write index
            # during decode, the shard offset under sequence parallelism,
            # 0 otherwise.  Keys are cached post-rotation, so the decode
            # path needs no re-rotation of the prefix.
            if ctx.state is not None and self._path in ctx.state:
                offset = ctx.get_state(self._path)["index"]
            elif self.sequence_axis is not None:
                from jax import lax
                offset = lax.axis_index(self.sequence_axis) * t
            else:
                offset = 0
            off = jnp.asarray(offset)
            # vector offset = per-slot decode positions: (B,) -> (B, t)
            pos = (off[..., None] + jnp.arange(t) if off.ndim
                   else offset + jnp.arange(t))
            q = rotary_embed(q, pos, self.rope_theta, self.rotary_dim)
            k = rotary_embed(k, pos, self.rope_theta, self.rotary_dim)
        if ctx.state is not None and self._path in ctx.state:
            # autoregressive decode: a KV cache was allocated for this layer
            # (TransformerLM.init_cache) — append this call's K/V at the
            # write index and attend over the cached prefix
            out = self._decode(ctx, q, k, v)
        elif self.sequence_axis is not None:
            from ..parallel.ring_attention import (ring_self_attention,
                                                   ulysses_self_attention)
            fn = (ring_self_attention if self.mode == "ring"
                  else ulysses_self_attention)
            out = fn(q, k, v, axis_name=self.sequence_axis,
                     causal=self.causal, impl=self.attn_impl)
        else:
            out = scaled_dot_product_attention(q, k, v, causal=self.causal,
                                               impl=self.attn_impl)
        out = out.reshape(b, t, self.q_dim)
        if gate is not None:
            out = out * jax.nn.sigmoid(gate)
        return self._out_proj(p, out)

    @staticmethod
    def _quantize_kv(x):
        """Symmetric per-(token, head) int8: x (B, t, H, D) -> (q int8,
        scale (B, t, H) f32).  amax over the head dim only, so one outlier
        token/head cannot flatten every other's resolution."""
        xf = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127
                     ).astype(jnp.int8)
        return q, scale

    def _decode(self, ctx, q, k, v):
        """Cached attention step.  q/k/v: (B, t, H, D) with t the number of
        new positions (t>1 = prefill, t=1 = one decode step).  The cache is
        this layer's entry of the call's state (nn/cache.py): the resident
        ``{"k": (B, H, D, Tmax), "v": ...}`` plus the write ``index`` — time
        is the LAST axis of every leaf (see :meth:`init_cache` for why); new
        keys land at columns [index, index+t) and queries see cache
        positions <= their own global position (cache columns past the
        index are masked, so the zeros there never contribute).

        With an int8 cache (``init_cache(dtype=jnp.int8)``) K/V are stored
        quantized with per-(token, head) symmetric scales and the scales are
        HOISTED out of both matmuls — scores multiply by ``k_scale`` on the
        (t, Tmax) tile and probabilities by ``v_scale`` before the PV
        matmul — so the big cache tensors cross HBM as int8 and are
        converted in the MXU tile load, never materialized dequantized.
        Long-context decode reads the cache, not the weights; halving its
        bytes halves the bandwidth bill where it dominates.

        A slot-decode step (``index`` a vector, ``t == 1``) over a float
        pool takes ONE Pallas call where :meth:`takes_slot_kernel` says so
        (tpu_dist.ops.decode_attention: it reads only each slot's resident
        blocks and writes only the block of the new column; a slot of
        length 0 is free there: untouched, output zero; grouped queries
        take its grouped form, ``G`` read from the shapes).  Prefill, a
        multi-token append, every CPU run and the int8 cache (its hoisted
        scales would be a different kernel; no benchmark cell runs it) stay
        on the dense branch below."""
        st = ctx.get_state(self._path)
        index = jnp.asarray(st["index"])
        t = q.shape[1]
        int8_cache = st["k"].dtype == jnp.int8
        new = {"k": k, "v": v}
        if int8_cache:
            new["k"], new["k_scale"] = self._quantize_kv(k)
            new["v"], new["v_scale"] = self._quantize_kv(v)
        # (B, t, ...) -> (B, ..., t): the stored order, time last
        new = {key: jnp.moveaxis(val, 1, -1).astype(st[key].dtype)
               for key, val in new.items()}
        group = self.num_heads // self.num_kv_heads
        if index.ndim == 1 and t == 1 and self.takes_slot_kernel(st):
            from ..ops.decode_attention import decode_attention
            with jax.named_scope("attend"):
                out, k_pool, v_pool = decode_attention(
                    q[:, 0], new["k"][..., 0], new["v"][..., 0], st["k"],
                    st["v"], index)
            ctx.put_state(self._path, dict(st, k=k_pool, v=v_pool,
                                           index=index + 1))
            return out[:, None]
        # scopes: the cache writes and the masked attention over the
        # whole cache are told apart in a device trace
        with jax.named_scope("cache_update"):
            if index.ndim:
                # per-slot write positions (continuous batching,
                # serve/engine): index is (B,) — every cache slot appends at
                # its OWN position and masks to its own prefix.  Rows whose
                # slot is free write garbage the next prefill fully
                # overwrites (and mask away).
                st = dict(st, **{
                    key: _write_columns(st[key], val, index)
                    for key, val in new.items()})
                cols = index[:, None] + jnp.arange(t)[None, :]    # (B, t)
                # (B, 1, t, Tmax): per-row causal+unwritten mask, broadcast
                # over heads
                mask = (jnp.arange(st["k"].shape[-1])[None, None, :]
                        <= cols[:, :, None])[:, None]
            else:
                st = dict(st, **{
                    key: jax.lax.dynamic_update_slice(
                        st[key], val, (0,) * (val.ndim - 1) + (index,))
                    for key, val in new.items()})
                qpos = index + jnp.arange(t)[:, None]       # (t, 1) global
                kpos = jnp.arange(st["k"].shape[-1])[None, :]  # (1, Tmax)
                mask = (kpos <= qpos)[None, None]     # causal + unwritten
            ctx.put_state(self._path, dict(st, index=index + t))
        with jax.named_scope("attend"):
            # the contractions read the pool where it lies, (B, H, D, Tmax):
            # for t == 1 they are multiply-reduce fusions over the stored
            # bytes, for prefill K is already K^T for the MXU.  Numerics are
            # scaled_dot_product_attention(impl="dense")'s: operands, scores
            # and softmax in the compute dtype; the int8 cache keeps float32
            # scores and its scales hoisted out of both matmuls.
            acc = jnp.float32 if int8_cache else None
            if group > 1:
                # K/V head j serves query heads [j * G, (j + 1) * G): the
                # G x t query rows of a group are one K/V head's batch
                b, heads = q.shape[0], self.num_kv_heads
                qg = q.reshape(b, t, heads, group, self.head_dim)
                s = jnp.einsum("btkgd,bkds->bkgts", qg,
                               st["k"].astype(q.dtype)
                               ) / math.sqrt(self.head_dim)
                w = jax.nn.softmax(jnp.where(mask[:, :, None], s, -jnp.inf),
                                   axis=-1)
                return jnp.einsum("bkgts,bkds->btkgd", w,
                                  st["v"].astype(q.dtype)).reshape(q.shape)
            s = jnp.einsum("bthd,bhds->bhts", q, st["k"].astype(q.dtype),
                           preferred_element_type=acc
                           ) / math.sqrt(self.head_dim)
            if int8_cache:
                s = s * st["k_scale"][:, :, None, :]
            w = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
            if int8_cache:
                w = (w * st["v_scale"][:, :, None, :]).astype(q.dtype)
            return jnp.einsum("bhts,bhds->bthd", w, st["v"].astype(q.dtype),
                              preferred_element_type=acc).astype(q.dtype)

    def takes_slot_kernel(self, entry) -> bool:
        """Whether a slot-decode step (a vector ``index``, one new position
        a slot) of THIS layer over its pool ``entry`` takes the Pallas
        kernel (tpu_dist.ops.decode_attention) or the dense branch of
        :meth:`_decode`.  Chosen as :func:`scaled_dot_product_attention`
        chooses flash: by what can be observed — a float pool whose ``D``
        fills whole sublane tiles and whose ``Tmax`` fills whole lanes, on
        a TPU backend (interpreted, the kernel would make every served
        token cost seconds) — with the trace-scoped :func:`attention_impl`
        (``"flash"`` / ``"dense"``) as the override.  One query row a K/V
        head or several: the kernel reads ``G`` off its operands' shapes.
        The engine asks the model, which asks here, which branch its decode
        program was built on."""
        from ..ops.decode_attention import decode_attention_ok
        return slot_kernel_wanted() and decode_attention_ok(entry["k"])

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """What this layer keeps per slot (one entry of a nn/cache.py tree,
        via TransformerLM.init_slot_cache): ``k``, ``v`` of shape
        ``(B, Hkv, D, Tmax)``, time LAST.  That is the
        layout the TPU compiler keeps such a pool in at rest whatever its
        logical shape (``Tmax`` in the 128 lanes, ``D`` in the sublanes:
        unpadded for bf16 whenever ``D % 16 == 0`` and ``Tmax % 128 == 0``,
        any head count); storing it so lets the decode program write the
        donated pool in place and the attention read it where it lies,
        with no whole-pool relayout copy (tests/test_decode_layout.py).
        ``dtype=jnp.int8`` allocates the quantized cache: int8 K/V plus
        float32 per-(token, head) scales ``(B, H, Tmax)`` (see
        :meth:`_decode`)."""
        if jnp.dtype(dtype) == jnp.int8 and self.num_kv_heads != \
                self.num_heads:
            raise ValueError("the int8 cache has no grouped-query form")
        shape = (batch, self.num_kv_heads, self.head_dim, max_len)
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        if jnp.dtype(dtype) == jnp.int8:
            cache["k_scale"] = jnp.zeros((batch, self.num_heads, max_len),
                                         jnp.float32)
            cache["v_scale"] = jnp.zeros((batch, self.num_heads, max_len),
                                         jnp.float32)
        return cache

    def __repr__(self):
        sp = (f", sequence_axis={self.sequence_axis!r}, mode={self.mode!r}"
              if self.sequence_axis else "")
        return (f"MultiheadSelfAttention({self.embed_dim}, "
                f"heads={self.num_heads}, causal={self.causal}{sp})")

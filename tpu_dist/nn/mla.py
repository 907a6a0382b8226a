"""Multi-head latent attention (DeepSeek-V2/V3's MLA, Kimi K2's block):
keys and values are rebuilt from ONE low-rank latent a position, and the
cache holds that latent, not K and V by head.

    c_q = N(x W_qa);            [q_nope | q_pe] = c_q W_qb        per head
    (``q_lora_rank`` None: [q_nope | q_pe] = x W_q, one projection, no rank)
    [c_kv | k_pe] = x W_kva;    c_kv <- N(c_kv)                   no heads
    q_pe, k_pe <- rope          (k_pe is ONE head, shared by all;
                                 ``use_nope``: no rotation anywhere)
    [k_nope | v] = c_kv W_kvb                                     per head
    scores = s (q_nope . k_nope + q_pe . k_pe);  causal softmax;  . v;  W_o

Per position the layer keeps the normalised ``c_kv`` and the (roped) ``k_pe``
side by side: ``kv_lora_rank + qk_rope_head_dim`` numbers (576 for Kimi K2,
against 64 x (192 + 128) by head), the ``latent`` leaf of nn/cache.py,
time-indexed and headless.

Two paths compute the same function of that latent and must agree
(tests/test_kimi_k2.py holds them together to 1e-5 in float32):

- EXPANDED (:meth:`MultiheadLatentAttention._expanded`): ``k_nope`` and
  ``v`` are rebuilt for every visible position through ``W_kvb`` and the
  attention is the usual one over heads of ``nope + rope`` / ``v``.  Right
  where many queries share the rebuilt keys: a plain forward and a prefill.
- ABSORBED (:meth:`MultiheadLatentAttention._absorbed`): ``W_kvb`` split per
  head into ``W_UK`` and ``W_UV``; ``q_lat = q_nope W_UK^T`` is folded into
  the query, the scores are ``s (q_lat . c_kv + q_pe . k_pe)`` over the
  latent ITSELF, every head's query rows against the one shared "head", the
  values are the first ``kv_lora_rank`` rows of the same columns, and
  ``W_UV`` is applied to the weighted latent.  Neither ``k_nope`` nor ``v``
  is ever built.  Right for a slot-decode step: one query row a head
  against thousands of resident columns.

Which path a call takes follows from the call: a vector ``index`` (one
write position a slot: ``TransformerLM.decode_step``) takes the absorbed
path, anything else the expanded one.  The expanded path of a WHOLE PROMPT
from position 0 (``index`` the Python int 0: ``TransformerLM.prefill_rows``)
computes its attention with the causal flash forward kernel where
:meth:`takes_prefill_kernel` says so (tpu_dist.ops.flash_attention, heads
of ``nope + rope`` for q and k and ``v`` for the values; operands built
heads first, ``out_proj`` contracting the result where it lies): no ``H x t
x t`` scores pass through HBM.  The plain differentiable forward, a
prefix-cache hit's suffix, a multi-token append, every CPU run and buckets
under 1,024 stay on two einsums around a materialised softmax
(:meth:`_expanded`).  The absorbed path's attention over
a slot pool is one Pallas call where :meth:`takes_slot_kernel` says so
(tpu_dist.ops.decode_attention.latent_decode_attention: it reads only each
slot's resident blocks and writes only the slab of the new column), and a
select of the new column into the whole pool and a read of all of it
everywhere else (every CPU run, a multi-token append).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import cache as kvcache
from . import functional as F
from . import init as I
from .attention import (_FLASH_MIN_SEQ, _write_columns, rotary_embed,
                        slot_kernel_wanted)
from .module import Module

__all__ = ["MultiheadLatentAttention"]


class MultiheadLatentAttention(Module):
    """Causal multi-head latent attention without biases.

    ``rope_inv_freq`` (``qk_rope_head_dim // 2`` values) replaces
    ``rope_theta``'s geometric frequencies (``nn.yarn_inv_freq``);
    ``softmax_scale`` replaces ``(nope + rope) ** -0.5`` (YaRN's
    ``mscale ** 2`` folded in by the caller).  The parameters follow the
    published layout: ``q_b_weight``'s columns are per head ``[nope |
    rope]``, ``kv_b_weight``'s per head ``[k_nope | v]``.

    Two forms a published configuration may ask for, under its own keys:
    ``q_lora_rank=None`` (``q_lora_rank: null``) projects the queries by
    ONE matrix ``q_weight`` ``(embed_dim, H (nope + rope))``, columns per
    head ``[nope | rope]`` as ``q_b_weight``'s, and has no ``q_a_weight`` /
    ``q_a_norm_weight`` / ``q_b_weight``; ``use_nope=True`` (``mla_use_nope:
    true``) rotates neither ``q_pe`` nor ``k_pe``: the layer has no
    positional encoding, its ``rope`` columns are ``qk_rope_head_dim`` more
    numbers of a key that all heads share, and ``rope_theta`` /
    ``rope_inv_freq`` go unread.  The cache, both paths and both kernels are
    the same in every form."""

    def __init__(self, embed_dim: int, num_heads: int, q_lora_rank,
                 kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: float = 10000.0, rope_inv_freq=None,
                 softmax_scale=None, norm_eps: float = 1e-6,
                 use_nope: bool = False):
        super().__init__()
        if qk_rope_head_dim % 2:
            raise ValueError(f"rotary embeddings need an even "
                             f"qk_rope_head_dim, got {qk_rope_head_dim}")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.nope, self.rope, self.v_dim = (qk_nope_head_dim,
                                            qk_rope_head_dim, v_head_dim)
        #: numbers the cache holds a position: the latent and the shared key
        self.latent_dim = kv_lora_rank + qk_rope_head_dim
        self.rope_theta = rope_theta
        self.rope_inv_freq = rope_inv_freq
        self.use_nope = use_nope
        self.softmax_scale = (softmax_scale if softmax_scale is not None
                              else (self.nope + self.rope) ** -0.5)
        self.norm_eps = norm_eps

    @property
    def attend_flops_per_position(self) -> int:
        """Operations one resident position costs one new query of this
        layer on the absorbed path: every head's row against the
        ``latent_dim`` numbers of the column, and its weight times the
        first ``kv_lora_rank`` of them."""
        return 2 * self.num_heads * (self.latent_dim + self.kv_lora_rank)

    def create_params(self, key):
        ks = jax.random.split(key, 5)
        d, h = self.embed_dim, self.num_heads
        lin = lambda k, fan_in, fan_out: I.torch_default_uniform(
            k, (fan_in, fan_out), fan_in)
        queries = ({"q_weight": lin(ks[0], d, h * (self.nope + self.rope))}
                   if self.q_lora_rank is None else
                   {"q_a_weight": lin(ks[0], d, self.q_lora_rank),
                    "q_a_norm_weight": jnp.ones((self.q_lora_rank,)),
                    "q_b_weight": lin(ks[1], self.q_lora_rank,
                                      h * (self.nope + self.rope))})
        return {
            **queries,
            "kv_a_weight": lin(ks[2], d, self.latent_dim),
            "kv_a_norm_weight": jnp.ones((self.kv_lora_rank,)),
            "kv_b_weight": lin(ks[3], self.kv_lora_rank,
                               h * (self.nope + self.v_dim)),
            "out_weight": lin(ks[4], h * self.v_dim, d)}

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """What this layer keeps per slot (one entry of a nn/cache.py
        tree): ``latent`` ``(B, kv_lora_rank + qk_rope_head_dim, Tmax)``,
        rows ``[0, kv_lora_rank)`` the normalised latent and the rest the
        roped shared key, time LAST as every pool here
        (:meth:`MultiheadSelfAttention.init_cache` says why), and no head
        axis: all heads read the same columns."""
        return {"latent": jnp.zeros((batch, self.latent_dim, max_len),
                                    dtype)}

    def takes_slot_kernel(self, entry) -> bool:
        """Whether a slot-decode step (a vector ``index``, one new position
        a slot) of THIS layer over its pool ``entry`` takes the Pallas
        kernel (tpu_dist.ops.decode_attention.latent_decode_attention:
        every head's query row against the resident columns alone, the new
        column written in place) or :meth:`forward`'s dense branch, which
        selects the new column into the whole pool and reads all of it.
        Chosen as :meth:`MultiheadSelfAttention.takes_slot_kernel` chooses:
        by what can be observed, a float pool whose latent and whose values
        fill whole sublane tiles and whose ``Tmax`` fills whole lanes, where
        :func:`slot_kernel_wanted` (a TPU backend; ``attention_impl``
        overrides)."""
        from ..ops.decode_attention import decode_attention_ok
        from ..ops._pallas import sublane_tile
        pool = entry["latent"]
        return (slot_kernel_wanted() and decode_attention_ok(pool)
                and self.kv_lora_rank % sublane_tile(pool.dtype) == 0)

    def takes_prefill_kernel(self, t, index) -> bool:
        """Whether a call of ``t`` new positions at write position
        ``index`` into a cache computes its EXPANDED attention with the
        causal flash forward kernel (tpu_dist.ops.flash_attention: scores
        in float32 inside the kernel, only the sub-tiles at or below the
        diagonal executed, nothing of ``H x t x t`` in HBM) or with two
        einsums around a materialised softmax.  Chosen, as
        :meth:`takes_slot_kernel` chooses, by what the call shows: a whole
        prompt from position 0 known while tracing (``index`` the Python
        int 0: ``TransformerLM.prefill_rows``), so that the mask is the
        plain causal one over the call's own ``t`` columns; ``t`` at least
        ``_FLASH_MIN_SEQ`` (below it the scores are small and XLA's fused
        dense path is the faster: the constant's own note); the q / k and
        the v head sizes whole multiples of the kernel's ``_D_ALIGN``; and
        :func:`slot_kernel_wanted` (a TPU backend; ``attention_impl``
        overrides).  Everything else stays dense: the plain differentiable
        forward (no cache; the kernel's backward takes one head size), a
        prefix-cache hit's suffix (a traced ``index``, keys longer than
        queries), a multi-token append, every CPU run, short buckets."""
        from ..ops.flash_attention import _D_ALIGN
        return (isinstance(index, int) and index == 0
                and t >= _FLASH_MIN_SEQ and slot_kernel_wanted()
                and (self.nope + self.rope) % _D_ALIGN == 0
                and self.v_dim % _D_ALIGN == 0)

    def prefill_pairs_executed(self, t, dtype=jnp.bfloat16) -> int:
        """(query, key) pairs ONE head of this layer executes for a whole
        prompt of ``t`` positions from position 0, of the ``t (t + 1) / 2``
        the mathematics needs: the kernel's executed sub-tiles
        (``tile_plan``, which counts by the kernels' own arithmetic) where
        :meth:`takes_prefill_kernel`, the whole square on the dense
        branch."""
        if not self.takes_prefill_kernel(t, 0):
            return t * t
        from ..ops.flash_attention import tile_plan
        plan = tile_plan(t, t, True, dtype=dtype)
        return plan["executed"] * plan["sub_q"] * plan["sub_k"]

    # -- the two paths over a latent ------------------------------------------

    def _w_kvb(self, p, dtype):
        """``W_kvb`` as ``(kv_lora_rank, H, nope + v)``: ``[..., :nope]`` is
        ``W_UK``, the rest ``W_UV``."""
        return p["kv_b_weight"].astype(dtype).reshape(
            self.kv_lora_rank, self.num_heads, self.nope + self.v_dim)

    def _softmax(self, scores, mask):
        return jax.nn.softmax(jnp.where(mask, scores * self.softmax_scale,
                                        -jnp.inf), axis=-1)

    def _expanded(self, p, q_nope, q_pe, latent, mask):
        """``q_nope`` (B, t, H, nope) and ``q_pe`` (B, t, H, rope) against
        ``latent`` (B, C, S), time last, with ``k_nope`` and ``v`` rebuilt
        for all S columns; ``mask`` broadcastable to (B, H, t, S), True =
        visible.  Returns (B, t, H, v)."""
        r = self.kv_lora_rank
        dtype = q_nope.dtype
        with jax.named_scope("expand"):
            kv = jnp.einsum("bcs,chd->bshd", latent[:, :r].astype(dtype),
                            self._w_kvb(p, dtype))
            k_pe = jnp.broadcast_to(
                jnp.swapaxes(latent[:, r:], 1, 2).astype(dtype)[:, :, None],
                kv.shape[:3] + (self.rope,))
            k = jnp.concatenate([kv[..., :self.nope], k_pe], axis=-1)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
        with jax.named_scope("attend"):
            w = self._softmax(jnp.einsum("bthd,bshd->bhts", q, k), mask)
            return jnp.einsum("bhts,bshd->bthd", w, kv[..., self.nope:])

    def _expanded_flash(self, p, q_nope, q_pe, latent):
        """:meth:`_expanded` for a whole prompt from position 0
        (:meth:`takes_prefill_kernel`): the same keys and values, causal,
        through the flash forward kernel.  Its operands are PRODUCED heads
        first, the kernel's own order: ``k_nope`` and ``v`` each by an
        einsum whose result is ``(B, H, S, d)``, the roped shared key
        broadcast into place once, the queries turned inside the fusion
        that ropes and joins them.  Returns ``(B, H, t, v)``, heads first
        too: :meth:`forward`'s ``out_proj`` contracts ``(h, d)`` where they
        lie."""
        from ..ops.flash_attention import flash_attention_heads_first
        r = self.kv_lora_rank
        dtype = q_nope.dtype
        with jax.named_scope("expand"):
            lat, w_kvb = latent[:, :r].astype(dtype), self._w_kvb(p, dtype)
            k_nope = jnp.einsum("bcs,chd->bhsd", lat, w_kvb[..., :self.nope])
            v = jnp.einsum("bcs,chd->bhsd", lat, w_kvb[..., self.nope:])
            k_pe = jnp.broadcast_to(
                jnp.swapaxes(latent[:, r:], 1, 2).astype(dtype)[:, None],
                k_nope.shape[:3] + (self.rope,))
            k = jnp.concatenate([k_nope, k_pe], axis=-1)
            q = jnp.swapaxes(jnp.concatenate([q_nope, q_pe], axis=-1), 1, 2)
        with jax.named_scope("attend"):
            return flash_attention_heads_first(
                q, k, v, causal=True, sm_scale=self.softmax_scale)

    def _absorbed(self, p, q_nope, q_pe, attend):
        """The same function with ``W_UK`` folded into the query and
        ``W_UV`` into the output: the attention runs over the latent's own
        columns, H x t query rows against one shared head.  ``attend`` maps
        the absorbed queries (B, t, H, C) to the weighted latent (B, t, H,
        kv_lora_rank): :meth:`_attend_latent` over a latent and a mask, or
        the decode kernel over a pool.  Returns (B, t, H, v)."""
        w_kvb = self._w_kvb(p, q_nope.dtype)
        with jax.named_scope("absorb"):
            q = jnp.concatenate(
                [jnp.einsum("bthd,chd->bthc", q_nope, w_kvb[..., :self.nope]),
                 q_pe], axis=-1)
        o_lat = attend(q)
        with jax.named_scope("absorb"):
            return jnp.einsum("bthc,chd->bthd", o_lat,
                              w_kvb[..., self.nope:])

    def _attend_latent(self, q, latent, mask):
        """Absorbed queries ``q`` (B, t, H, C) against ``latent`` (B, C,
        S), densely; ``mask`` as :meth:`_expanded`'s."""
        with jax.named_scope("attend"):
            lat = latent.astype(q.dtype)
            w = self._softmax(jnp.einsum("bthc,bcs->bhts", q, lat), mask)
            return jnp.einsum("bhts,bcs->bthc", w,
                              lat[:, :self.kv_lora_rank])

    # -- forward ----------------------------------------------------------------

    def forward(self, x):
        from .module import _ctx
        ctx = _ctx()
        p = ctx.get_params(self._path)
        b, t, _ = x.shape
        h, r = self.num_heads, self.kv_lora_rank
        st = (ctx.get_state(self._path)
              if ctx.state is not None and self._path in ctx.state else None)
        # this call's write position: a Python int or a scalar when every
        # row writes at one position, a (B,) vector for a slot step
        index = st["index"] if st is not None else 0
        vector = getattr(index, "ndim", 0) == 1
        steps = jnp.arange(t)
        pos = index[:, None] + steps if vector else index + steps
        rope = ((lambda a: a) if self.use_nope else
                (lambda a: rotary_embed(a, pos, self.rope_theta,
                                        inv_freq=self.rope_inv_freq)))
        with jax.named_scope("q_proj" if self.q_lora_rank is None
                             else "q_lora"):
            if self.q_lora_rank is None:
                q = F.linear(x, p["q_weight"])
            else:
                c_q = F.rms_norm(F.linear(x, p["q_a_weight"]),
                                 p["q_a_norm_weight"], self.norm_eps)
                q = F.linear(c_q, p["q_b_weight"])
            q = q.reshape(b, t, h, self.nope + self.rope)
            q_nope = q[..., :self.nope]
            q_pe = rope(q[..., self.nope:])
        with jax.named_scope("kv_latent"):
            kv = F.linear(x, p["kv_a_weight"])
            c_kv = F.rms_norm(kv[..., :r], p["kv_a_norm_weight"],
                              self.norm_eps)
            k_pe = rope(kv[..., None, r:])[..., 0, :]
            # (B, t, C) -> (B, C, t): the stored order, time last
            new = jnp.swapaxes(jnp.concatenate([c_kv, k_pe], axis=-1), 1, 2)
        heads_first = False
        if st is None:
            out = self._expanded(p, q_nope, q_pe, new,
                                 steps[None, :] <= steps[:, None])
        elif vector and t == 1 and self.takes_slot_kernel(st):
            from ..ops.decode_attention import latent_decode_attention

            def attend(q):
                with jax.named_scope("attend"):
                    o_lat, pool = latent_decode_attention(
                        q[:, 0], new[..., 0], st["latent"], index,
                        value_dim=r, scale=self.softmax_scale)
                ctx.put_state(self._path, dict(st, latent=pool,
                                               index=index + 1))
                return o_lat[:, None]

            out = self._absorbed(p, q_nope, q_pe, attend)
        else:
            pool = st["latent"]
            new = new.astype(pool.dtype)
            tmax = pool.shape[kvcache.time_axis(pool)]
            with jax.named_scope("cache_update"):
                if vector:
                    # per-slot write positions; a free slot's row writes a
                    # column its next prefill overwrites
                    pool = _write_columns(pool, new, index)
                    latent = pool
                    mask = (jnp.arange(tmax)[None, None, :]
                            <= pos[:, :, None])[:, None]     # (B, 1, t, Tmax)
                else:
                    pool = jax.lax.dynamic_update_slice(pool, new,
                                                        (0, 0, index))
                ctx.put_state(self._path, dict(st, latent=pool,
                                               index=index + t))
            if vector:
                out = self._absorbed(p, q_nope, q_pe, lambda q:
                                     self._attend_latent(q, latent, mask))
            elif self.takes_prefill_kernel(t, index):
                # the call's own columns ARE the visible ones, in the
                # stored type: the causal kernel over them, heads first
                heads_first = True
                out = self._expanded_flash(p, q_nope, q_pe, new)
            else:
                with jax.named_scope("cache_update"):
                    # the columns this call can see: all the rows hold,
                    # unless the position is known while tracing (a whole
                    # prompt from 0: TransformerLM.prefill_rows), when the
                    # keys rebuilt and the scores stop at the last one
                    seen = (min(tmax, index + t) if isinstance(index, int)
                            else tmax)
                    latent = kvcache.time_slice(pool, 0, seen)
                    mask = jnp.arange(seen)[None, :] <= pos[:, None]
                out = self._expanded(p, q_nope, q_pe, latent, mask)
        with jax.named_scope("out_proj"):
            if heads_first:     # (B, H, t, v): contract (h, d) where they lie
                return jnp.einsum("bhtd,hdo->bto", out,
                                  p["out_weight"].reshape(h, self.v_dim, -1))
            return F.linear(out.reshape(b, t, h * self.v_dim),
                            p["out_weight"])

    def __repr__(self):
        return (f"MultiheadLatentAttention({self.embed_dim}, "
                f"heads={self.num_heads}, latent={self.latent_dim})")

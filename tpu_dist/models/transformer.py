"""Decoder-only Transformer LM — the long-context workload.

No counterpart in the reference (its workloads are image classifiers,
SURVEY.md §2a); this model exists because tpu_dist treats sequence
parallelism as first-class: with ``sequence_axis`` set, every attention
layer runs ring (or Ulysses) attention over the mesh's sequence axis and
the same model trains on contexts far beyond one core's memory.

Architecture: pre-LN blocks (LN → MHSA → residual, LN → MLP(4x, GELU) →
residual), learned positional embeddings, weight-untied LM head.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..nn.module import current_context, run_capturing_state

__all__ = ["TransformerLM", "TransformerBlock"]


def _make_norm(norm: str, dim: int, eps: Optional[float] = None):
    """``eps=None`` keeps the norm class's own default (LayerNorm 1e-5,
    RMSNorm 1e-6); ViT passes 1e-6 for torchvision parity, OLMoE 1e-5.
    ``"rmsnorm_zc"`` is the zero-centred RMSNorm, ``x_hat * (1 + w)``."""
    if norm not in ("layernorm", "rmsnorm", "rmsnorm_zc"):
        raise ValueError(f"Unknown norm {norm!r} "
                         f"(layernorm|rmsnorm|rmsnorm_zc)")
    kw = {} if eps is None else {"eps": eps}
    if norm == "layernorm":
        return nn.LayerNorm(dim, **kw)
    return nn.RMSNorm(dim, zero_centered=norm == "rmsnorm_zc", **kw)


class TransformerBlock(nn.Module):
    def __init__(self, dim: int, num_heads: int, causal: bool = True,
                 sequence_axis: Optional[str] = None, mode: str = "ring",
                 mlp: Optional[nn.Module] = None, norm: str = "layernorm",
                 rope: bool = False, rope_theta: float = 10000.0,
                 norm_eps: Optional[float] = None, attn_bias: bool = True,
                 qk_norm: bool = False, mixer: Optional[nn.Module] = None,
                 residual=None):
        """``mixer`` overrides the token mixer (the ``attn`` submodule)
        with a module built by the caller, as ``mlp`` overrides the MLP:
        e.g. an :class:`nn.GatedDeltaNet`, or an attention layer spelled
        beyond this constructor's flags.  A mixer that serves from a slot
        cache has ``init_cache(batch, max_len, dtype)``; a composite of
        several such (:class:`nn.ParallelMixer`: one norm, mixers side by
        side, outputs summed into the residual) has ``mixers()``, the parts
        that each own a cache entry.

        ``residual`` is how a sublayer's output joins the residual: None is
        ``x + f(x)``; a callable builds the joining module of ONE sublayer
        (called twice: ``hc_attn`` and ``hc_mlp``, each with parameters of
        its own), e.g. ``lambda: nn.HyperConnection(dim, 4)``, and the block
        then takes and returns that module's ``streams`` streams, a tuple of
        ``(B, T, dim)`` arrays (:attr:`streams`)."""
        super().__init__()
        self.ln1 = _make_norm(norm, dim, norm_eps)
        # qk_norm: an RMSNorm over the whole q and k projections, with the
        # block's own eps (OLMoE)
        self.attn = mixer if mixer is not None else nn.MultiheadSelfAttention(
            dim, num_heads, bias=attn_bias, causal=causal,
            sequence_axis=sequence_axis, mode=mode, rope=rope,
            rope_theta=rope_theta, qk_norm=qk_norm,
            qk_norm_eps=1e-6 if norm_eps is None else norm_eps)
        self.ln2 = _make_norm(norm, dim, norm_eps)
        # mlp override: e.g. an nn.MoELayer for mixture-of-experts blocks
        self.mlp = mlp if mlp is not None else nn.Sequential(
            nn.Linear(dim, 4 * dim), nn.GELU(), nn.Linear(4 * dim, dim))
        # registered only where asked for: a module more moves every later
        # module's initialisation key
        self.hc_attn = self.hc_mlp = None
        if residual is not None:
            self.hc_attn, self.hc_mlp = residual(), residual()

    @property
    def streams(self) -> int:
        """Residual streams the block takes and returns (1: one array)."""
        return 1 if self.hc_attn is None else self.hc_attn.streams

    def forward(self, x):
        if self.hc_attn is None:
            x = x + self.attn(self.ln1(x))
            x = x + self.mlp(self.ln2(x))
            return x
        u, coeff = self.hc_attn(x)
        x = self.hc_attn.post(x, self.attn(self.ln1(u)), coeff)
        u, coeff = self.hc_mlp(x)
        return self.hc_mlp.post(x, self.mlp(self.ln2(u)), coeff)


class TransformerLM(nn.Module):
    """Causal LM: tokens (B, T) → logits (B, T, vocab).

    ``sequence_axis``: mesh axis name for sequence parallelism.  Embeddings
    are computed on the local sequence shard; the shard's global position
    offset is derived **automatically** from ``lax.axis_index(sequence_axis)``
    when tracing inside ``shard_map`` — callers never plumb it.  Pass
    ``pos_offset`` only to override (e.g. sliding-window training on
    unsharded models).
    """

    def __init__(self, vocab_size: int, dim: int = 128, depth: int = 2,
                 num_heads: int = 4, max_seq_len: int = 1024,
                 causal: bool = True, sequence_axis: Optional[str] = None,
                 mode: str = "ring", remat: bool = False,
                 num_experts: int = 0, moe_top_k: int = 2,
                 moe_every: int = 1, moe_capacity_factor: float = 1.25,
                 moe_dispatch: str = "einsum", moe_hidden: int = 0,
                 moe_gated: bool = False, moe_normalize_gates: bool = True,
                 norm: str = "layernorm", rope: bool = False,
                 rope_theta: float = 10000.0,
                 norm_eps: Optional[float] = None, attn_bias: bool = True,
                 qk_norm: bool = False):
        """``num_experts > 0`` makes every ``moe_every``-th block's MLP a
        routed :class:`~tpu_dist.nn.MoELayer` (expert-parallel under
        :data:`~tpu_dist.parallel.MOE_EP_RULES`); aux load-balance losses
        surface in the model state, see nn/moe.py.  ``moe_dispatch=
        "gather"`` selects the index-map dispatch (cheaper off the GSPMD
        'expert' axis — see nn/moe.py), ``"dropless"`` the grouped-matmul
        one, which drops no row and is the one to serve with.
        ``moe_hidden`` is one expert's width (0 = ``4 * dim``),
        ``moe_gated`` makes the expert ``down(silu(gate(x)) * up(x))``
        without biases, ``moe_normalize_gates=False`` uses the top-k router
        probabilities as they are.

        ``norm_eps`` sets every norm's eps (None keeps each class's own),
        ``attn_bias=False`` drops the attention projections' biases,
        ``qk_norm`` normalises the whole q and k projections before the
        head split (nn/attention.py).  With the LLaMA-family recipe below
        these spell OLMoE (chipbench/configs/olmoe-1b-7b-serve.json).

        ``norm="rmsnorm"`` + ``rope=True`` gives the LLaMA-family recipe:
        RMS normalization and rotary position embeddings instead of the
        learned position table (``self.pos`` is then absent — attention
        scores depend only on relative distance)."""
        super().__init__()
        if num_experts > 0 and moe_every < 1:
            raise ValueError(f"moe_every must be >= 1, got {moe_every}")
        self.num_experts = num_experts

        def block(i):
            moe = (num_experts > 0 and i % moe_every == moe_every - 1)
            return TransformerBlock(
                dim, num_heads, causal=causal,
                sequence_axis=sequence_axis, mode=mode, norm=norm,
                rope=rope, rope_theta=rope_theta, norm_eps=norm_eps,
                attn_bias=attn_bias, qk_norm=qk_norm,
                mlp=nn.MoELayer(dim, num_experts, hidden=moe_hidden,
                                top_k=moe_top_k,
                                capacity_factor=moe_capacity_factor,
                                normalize_gates=moe_normalize_gates,
                                dispatch=moe_dispatch, gated=moe_gated)
                if moe else None)

        self._assemble(vocab_size, dim, max_seq_len,
                       [block(i) for i in range(depth)],
                       ln_f=_make_norm(norm, dim, norm_eps),
                       head=nn.Linear(dim, vocab_size), learned_pos=not rope,
                       causal=causal, sequence_axis=sequence_axis,
                       remat=remat)

    def _assemble(self, vocab_size: int, dim: int, max_seq_len: int, blocks,
                  ln_f, head, learned_pos: bool, causal: bool = True,
                  sequence_axis: Optional[str] = None, remat: bool = False,
                  embedding_multiplier: float = 1.0,
                  head_multiplier: float = 1.0):
        """Register the model's parts.  A model spelled beyond
        ``__init__``'s flags (models/qwen3_next.py) builds its own blocks,
        each a :class:`TransformerBlock` whose ``attn`` is any token mixer,
        and shares everything below: embedding, forward, the slot cache,
        the pool programs' two methods and :meth:`generate`.
        ``embedding_multiplier`` scales the token embeddings and
        ``head_multiplier`` the logits, constants of the program
        (Falcon-H1's; 1 is no operation)."""
        self.vocab_size = vocab_size
        self.embedding_multiplier = float(embedding_multiplier)
        self.head_multiplier = float(head_multiplier)
        self.max_seq_len = max_seq_len
        self.tok = nn.Embedding(vocab_size, dim)
        self.pos = nn.Embedding(max_seq_len, dim) if learned_pos else None
        for i, blk in enumerate(blocks):
            setattr(self, f"block{i}", blk)
        self.depth = len(blocks)
        #: residual streams between the blocks (the blocks' own answer):
        #: ``forward`` opens them after the embedding and closes them before
        #: ``ln_f``; 1 is the plain residual, one array and neither step
        self.streams = blocks[0].streams if blocks else 1
        self.causal = causal
        self.sequence_axis = sequence_axis
        # remat=True wraps each block in jax.checkpoint: activations inside
        # a block are recomputed during backward instead of living in HBM
        # for the whole step — the standard long-context memory/FLOPs trade
        # (per-layer residual-boundary policy, like torch's
        # checkpoint_sequential over blocks)
        self.remat = remat
        self.ln_f = ln_f
        self.head = head

    def _mixers(self):
        """The token mixers that own a cache entry, in order: each block's
        ``attn``, or, where that is a composite of several
        (:class:`nn.ParallelMixer`), its ``mixers()`` in its order.  So a
        layer of two mixers side by side is two entries here, and a method
        that walks this list sees both."""
        mixers = (getattr(self, f"block{i}").attn for i in range(self.depth))
        return [part for mixer in mixers
                for part in (mixer.mixers() if hasattr(mixer, "mixers")
                             else [mixer])]

    def embed_tokens(self, idx, pos_offset=None):
        """Token (+ learned positional) embeddings for ``idx`` (B, T) —
        the input half of :meth:`forward`, factored out so the
        tensor-parallel serving path (tpu_dist/serve/sharded.py) runs the
        byte-identical embedding on every shard.  ``pos_offset`` may be a
        scalar or a (B,) vector (per-slot decode positions)."""
        t = idx.shape[1]
        if pos_offset is None:
            if self.sequence_axis is not None:
                from jax import lax
                pos_offset = lax.axis_index(self.sequence_axis) * t
            else:
                pos_offset = 0
        if self.pos is not None:
            off = jnp.asarray(pos_offset)
            # vector pos_offset = per-slot decode positions (decode_step):
            # (B,) offsets index a (B, t) position table row per sequence
            pos_idx = (off[..., None] + jnp.arange(t) if off.ndim
                       else pos_offset + jnp.arange(t))
            return nn.functional.scaled(self.tok(idx) + self.pos(pos_idx),
                                        self.embedding_multiplier)
        # rope: positions enter through the attention rotations
        return nn.functional.scaled(self.tok(idx), self.embedding_multiplier)

    def forward(self, idx, pos_offset=None):
        x = self.embed_tokens(idx, pos_offset)
        # remat is a training-memory trade; during cached decode it must be
        # off — the attention layers' put_state writes would leak tracers
        # out of the jax.checkpoint sub-trace (and inference keeps no
        # activations anyway)
        use_remat = self.remat and not self._decoding()
        if self.streams > 1:
            x = nn.open_streams(x, self.streams)    # no operation, no scope
        for i in range(self.depth):
            block = getattr(self, f"block{i}")
            if use_remat:
                # params reach the block through the apply() context as
                # closed-over tracers; jax.checkpoint differentiates through
                # closures, so no explicit param plumbing is needed.  State
                # updates (MoE aux losses) must NOT be written to the outer
                # context from inside the remat sub-trace — that leaks
                # tracers — so they are captured and returned as explicit
                # checkpoint outputs, then re-published outside.
                x, updates = jax.checkpoint(
                    lambda y, _b=block: run_capturing_state(_b, (y,)))(x)
                ctx = current_context()
                for path, val in updates.items():
                    ctx.put_state(path, val)
            else:
                x = block(x)
        if self.streams > 1:
            x = nn.close_streams(x)
        return nn.functional.scaled(self.head(self.ln_f(x)),
                                    self.head_multiplier)

    def residual_numbers_per_row(self) -> int:
        """Numbers of the compute type ONE row (a prompt token, a busy slot)
        must move through the residual mixes of all sublayers: each block's
        joining modules' ``numbers_per_row``; 0 for the plain residual,
        whose add rides in the sublayer's own output.  A host fact for
        ``SlotEngine.stats()["residual"]``."""
        return sum(hc.numbers_per_row
                   for i in range(self.depth)
                   for hc in (getattr(self, f"block{i}").hc_attn,
                              getattr(self, f"block{i}").hc_mlp)
                   if hc is not None)

    def _decoding(self) -> bool:
        """True when the current apply() carries a cache entry for any of
        this model's cache-owning mixers (:meth:`_mixers`: either part of a
        layer of two), i.e. we are inside prefill/decode."""
        from ..nn.module import current_context
        ctx = current_context()
        if ctx is None or not ctx.state:
            return False
        return any(mixer._path in ctx.state for mixer in self._mixers())

    # -- autoregressive inference ------------------------------------------

    def init_cache(self, batch: int, max_len: Optional[int] = None,
                   dtype=jnp.float32):
        """The state :meth:`generate` starts from, for a caller that drives
        ``apply(state=...)`` itself: :meth:`init_slot_cache` addressed at
        position 0 (``nn.cache.call_state``).  Every such call returns the
        state advanced by its tokens, ready for the next."""
        return nn.cache.call_state(
            self.init_slot_cache(batch, max_len, dtype),
            jnp.zeros((), jnp.int32))

    # -- slot-pool decode (continuous batching; tpu_dist.serve) ------------

    def init_slot_cache(self, slots: int, max_len: Optional[int] = None,
                        dtype=jnp.float32):
        """Cache pool for slot-based continuous-batching decode, in the
        format nn/cache.py owns: per cache-owning mixer (:meth:`_mixers`;
        a layer of two mixers side by side has two entries, one a part,
        say ``block3.attn.attention`` with ``k``/``v`` and
        ``block3.attn.ssm`` with ``state``/``conv``), keyed by module path,
        what the mixer keeps per slot: an attention layer's ``k``/``v``
        ``(slots, Hkv, D, max_len)``, time last (the layout the TPU
        compiler keeps the pool in, written and read in place;
        :meth:`nn.MultiheadSelfAttention.init_cache`), a recurrent layer's
        whole state (:meth:`nn.GatedDeltaNet.init_cache`).  No write position
        is stored: each call to :meth:`decode_step` supplies every slot's
        as the ``lengths`` vector, so the host-side engine
        (:class:`tpu_dist.serve.SlotEngine`) holds the single source of
        truth for slot occupancy."""
        if self.sequence_axis is not None:
            raise ValueError("KV-cache decode runs on gathered sequences; "
                             "build the model without sequence_axis for "
                             "generation")
        if not self.causal:
            raise ValueError("KV-cache decode requires causal attention: a "
                             "bidirectional model's logits depend on future "
                             "tokens and cannot be decoded incrementally")
        max_len = self.max_seq_len if max_len is None else max_len
        self._assign_paths()
        return {mixer._path: mixer.init_cache(slots, max_len, dtype)
                for mixer in self._mixers()}

    def slot_decode_kernel(self, cache) -> bool:
        """Whether a decode step over the pool ``cache`` takes a Pallas
        decode-attention kernel in EVERY mixer that keeps a time-indexed
        pool, by head or latent (``nn.cache.pool_leaf``; each mixer's own
        answer, its ``takes_slot_kernel``; of a layer of two mixers, the
        part that keeps the pool)."""
        return all(mixer.takes_slot_kernel(cache[mixer._path])
                   for mixer in self._mixers()
                   if nn.cache.pool_leaf(cache[mixer._path]) is not None)

    def slot_state_kernel(self, cache) -> bool:
        """Whether a decode step over the pool ``cache`` computes EVERY
        recurrent layer's one-token update with the Pallas kernel
        (tpu_dist.ops.delta_step; each layer's own answer, its
        ``takes_step_kernel``, asked under the ``attention_impl`` the
        program is traced under; of a layer of two mixers, the part that
        keeps the state).  False for a model that keeps no whole state.  A
        host fact for ``SlotEngine.stats()["state"]``."""
        layers = [m for m in self._mixers() if hasattr(m, "takes_step_kernel")]
        return bool(layers) and all(m.takes_step_kernel(cache[m._path])
                                    for m in layers)

    def prefill_scan_kernel(self, cache, bucket: int) -> bool:
        """Whether a whole-prompt prefill of ``bucket`` positions into the
        pool ``cache`` computes EVERY recurrent layer's scan with the Pallas
        kernel (tpu_dist.ops.delta_scan; each layer's own answer, its
        ``takes_scan_kernel``, asked under the ``attention_impl`` the
        program is traced under; of a layer of two mixers, the part that
        keeps the state).  False for a model that keeps no whole state.  A
        host fact for ``SlotEngine.stats()["prefill_scan"]``."""
        layers = [m for m in self._mixers() if hasattr(m, "takes_scan_kernel")]
        return bool(layers) and all(
            m.takes_scan_kernel(cache[m._path], bucket) for m in layers)

    def prefill_attention_facts(self, bucket: int, dtype=jnp.bfloat16) -> dict:
        """What the attention of a whole-prompt prefill of ``bucket``
        positions is built on, in the layers that say (a latent layer's
        ``takes_prefill_kernel`` / ``prefill_pairs_executed``; asked under
        the ``attention_impl`` the program is traced under): ``kernel``,
        whether every such layer takes the causal flash forward kernel;
        ``heads``, query heads summed over those layers; ``pairs_executed``,
        the (query, key) pairs they execute, all heads.  A model with no
        such layer answers ``False, 0, 0``.  Host facts for
        ``SlotEngine.stats()["prefill_attn"]``."""
        layers = [m for m in self._mixers()
                  if hasattr(m, "takes_prefill_kernel")]
        return {"kernel": bool(layers) and all(
                    m.takes_prefill_kernel(bucket, 0) for m in layers),
                "heads": sum(m.num_heads for m in layers),
                "pairs_executed": sum(
                    m.num_heads * m.prefill_pairs_executed(bucket, dtype)
                    for m in layers)}

    def init_moe_counters(self):
        """Routed-row counters for serving a model with expert layers, one
        entry per :class:`~tpu_dist.nn.MoELayer` keyed by its path (empty
        for a dense model; :meth:`nn.MoELayer.init_counters` names the
        leaves).  Given to :meth:`decode_step` / :meth:`prefill_into_slot`
        BESIDE the cache, they come back with the call's rows added — on
        the device, nothing is read back (tpu_dist.serve.SlotEngine keeps a
        set per pool program)."""
        self._assign_paths()
        return {mlp._path: mlp.init_counters()
                for mlp in (getattr(self, f"block{i}").mlp
                            for i in range(self.depth))
                if isinstance(mlp, nn.MoELayer)}

    def decode_step(self, params, tokens, lengths, cache, counters=None):
        """ONE decode iteration over a slot pool: feed each slot's current
        last token, get each slot's next-token logits.

        ``tokens``: (B,) int — the token each slot decoded last (or the
        prompt's last token right after prefill).  ``lengths``: (B,) int —
        tokens already resident in each slot's cache row, i.e. the write
        position.  ``cache``: from :meth:`init_slot_cache` /
        :meth:`prefill_into_slot`.  ``counters``: from
        :meth:`init_moe_counters`, or None; they take a slot of length 0 as
        free.  Returns ``(logits (B, vocab), new_cache, new_counters)``.
        Free slots decode garbage rows the caller masks; their cache writes
        land in rows the next prefill overwrites.  The math per row is
        exactly :meth:`generate`'s decode scan — the scan *uses* this
        method — so slot decode and offline generation cannot drift."""
        lengths = jnp.asarray(lengths, jnp.int32)
        state = nn.cache.call_state(cache, lengths, counters,
                                    valid=(lengths > 0)[:, None])
        tokens = jnp.asarray(tokens)[:, None]
        logits, state = self.apply(params, tokens, pos_offset=lengths,
                                   state=state)
        return (logits[:, -1], *nn.cache.split_state(state, counters))

    def prefill_into_slot(self, params, prompt, length, slot, cache,
                          counters=None):
        """Prefill ONE request into slot ``slot`` of a slot-cache pool
        while other slots' rows are untouched — the admission half of
        continuous batching: :meth:`prefill_rows` at the PROMPT's padded
        extent and in the pool's type, then ``nn.cache.write_slot_rows``
        (the rows' columns land at column 0 of the slot; the slot's columns
        past them keep what its last request left, which no decode step
        attends before it has overwritten it).

        ``prompt``: (S,) int tokens, padded past ``length`` with any valid
        token id (padding K/V lands at positions ``>= length``, which
        every later decode step either masks out or overwrites before
        attending).  ``length``: true token count (traced OK).  Returns
        ``(last-real-token logits (vocab,), new_cache, new_counters)`` —
        sample the request's first generated token from those logits.  One
        padded prompt length = one compiled program; bucket prompt lengths
        to bound retraces.

        SEVERAL requests in one forward: ``prompt`` (P, S), ``length`` and
        ``slot`` (P,); row ``i`` lands in ``slot[i]`` and the logits are
        (P, vocab).  Every layer's weights are read once for the P prompts.
        A row of ``length`` 0 is an ABSENT prompt: all its positions are
        padding (the counters take them as such), it leaves nothing in
        the pool and its logits are nobody's."""
        logits, rows, counters = self.prefill_rows(
            params, prompt, length, jnp.shape(prompt)[-1],
            nn.cache.extent(cache)[1], counters=counters)
        # a program of one prompt has no absent row
        present = (jnp.asarray(length) > 0 if jnp.size(slot) > 1 else None)
        return (logits, nn.cache.write_slot_rows(cache, rows, slot, present),
                counters)

    def prefill_rows(self, params, prompt, length, max_len,
                     dtype=jnp.float32, prefix_rows=None, prefix_len=0,
                     counters=None):
        """Prefill ONE request into fresh batch-1 cache rows with NO slot
        pool in sight — the forward of :meth:`prefill_into_slot`, and the
        disaggregated-prefill primitive: a prefill rank computes these rows
        and ships them to a decode rank, where ``nn.cache.write_slot_rows``
        lands them in a free slot.

        ``prompt``: (S,) int suffix tokens, padded past the true suffix
        length with any valid id.  ``length``: TOTAL true token count
        including any cached prefix.  With ``prefix_rows`` (batch-1 rows
        holding the first ``prefix_len`` tokens' K/V — a prefix-cache
        hit), only the suffix runs the forward: positions start at
        ``prefix_len`` (learned table via ``pos_offset``, rope via the
        cache write index) and the suffix K/V appends at
        ``[prefix_len, prefix_len + S)``.  ``counters``
        (:meth:`init_moe_counters`) take the rows past the true length as
        padding.  Returns ``(last-real-token logits (vocab,), rows,
        new_counters)`` where ``rows`` are full-width per-layer entries,
        ``k``/``v`` of shape ``(1, H, D, max_len)`` — the pool's own order,
        time last, so the slot write lands them without a transpose.  One
        padded suffix length = one compiled program.

        ``prompt`` (P, S) with ``length`` (P,) is a GROUP of whole prompts
        in one forward at batch P: logits (P, vocab), each row's at its own
        last real position, and batch-P ``rows``.  A prefix hit's suffix
        goes alone."""
        prompt = jnp.asarray(prompt)
        group = prompt.ndim == 2
        prompts = prompt if group else prompt[None, :]
        # each prompt's true tokens
        real = jnp.asarray(length, jnp.int32).reshape(-1)
        if prefix_rows is None:
            rows = self.init_slot_cache(prompts.shape[0], max_len, dtype)
            # whole prompts from position 0, known while tracing: a layer
            # may stop at the columns such a call can see
            # (nn.MultiheadLatentAttention.forward)
            start, offset = 0, None
        else:
            if group:
                raise ValueError(
                    "a prefix hit's suffix is prefilled alone: its rows "
                    "start at the hit's own position, which a group of "
                    f"{prompts.shape[0]} prompts does not share")
            rows = prefix_rows
            start = offset = jnp.asarray(prefix_len, jnp.int32)
            real = real - start
        state = nn.cache.call_state(
            rows, start, counters,
            valid=jnp.arange(prompts.shape[1])[None, :] < real[:, None])
        logits, state = self.apply(params, prompts, pos_offset=offset,
                                   state=state)
        last = [jax.lax.dynamic_index_in_dim(row, n - 1, axis=0,
                                             keepdims=False)
                for row, n in zip(logits, real)]
        return (jnp.stack(last) if group else last[0],
                *nn.cache.split_state(state, counters))

    def generate(self, params, prompt, max_new_tokens: int,
                 temperature: float = 0.0, rng=None, cache_dtype=None,
                 top_k: int = 0, top_p: float = 1.0):
        """Autoregressive decoding with a KV cache.

        ``prompt``: int tokens (B, Tp).  Returns (B, Tp + max_new_tokens) —
        the prompt with the continuation appended.  ``temperature`` 0 is
        greedy argmax; > 0 samples categorically (``rng`` required), with
        optional truncation: ``top_k`` > 0 restricts sampling to the k
        highest-probability tokens, ``top_p`` < 1 to the smallest set
        whose cumulative probability reaches p (nucleus sampling; the
        highest-probability token always stays eligible).  Both filters
        are static-shape masks over the fixed vocab, so they trace into
        the same single XLA program.  The prompt is prefilled in ONE
        forward pass (cache index advances by Tp), then each new token is
        one t=1 forward through the cache — the whole loop is a
        ``lax.scan``, so generate() jits with no per-token dispatch.
        """
        b, tp = prompt.shape
        if max_new_tokens <= 0:
            if max_new_tokens == 0:
                return prompt
            raise ValueError(f"max_new_tokens must be >= 0, got "
                             f"{max_new_tokens}")
        total = tp + max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(f"prompt ({tp}) + max_new_tokens "
                             f"({max_new_tokens}) exceeds max_seq_len "
                             f"({self.max_seq_len})")
        if temperature > 0 and rng is None:
            raise ValueError("temperature > 0 sampling requires rng=")
        if top_k < 0 or top_k > self.vocab_size:
            raise ValueError(f"top_k must be in [0, vocab_size], got "
                             f"{top_k}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")

        def sample(logits, key):
            if temperature <= 0:
                return logits.argmax(-1)
            logits = logits / temperature
            if top_k:
                kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            if top_p < 1.0:
                desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
                probs = jax.nn.softmax(desc, axis=-1)
                # keep tokens whose cumulative probability BEFORE them is
                # < p: the argmax token (exclusive cumsum 0) always stays
                keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
                thresh = jnp.min(jnp.where(keep, desc, jnp.inf),
                                 axis=-1, keepdims=True)
                logits = jnp.where(logits < thresh, -jnp.inf, logits)
            return jax.random.categorical(key, logits, axis=-1)

        logits, state = self.apply(
            params, prompt,
            state=self.init_cache(b, total, cache_dtype or jnp.float32))
        key0 = rng if rng is not None else jax.random.key(0)
        first = sample(logits[:, -1], jax.random.fold_in(key0, 0))
        # the decode loop runs on the slot-pool primitive (decode_step):
        # lengths = tp + i for every row, so offline generation and the
        # serving engine's continuous-batching decode share ONE code path
        slot_cache, _ = nn.cache.split_state(state)

        def step(carry, i):
            tok, cache = carry
            lengths = jnp.full((b,), tp, jnp.int32) + i
            logits, cache, _ = self.decode_step(params, tok, lengths, cache)
            nxt = sample(logits, jax.random.fold_in(key0, i + 1))
            return (nxt, cache), tok

        (last, _), toks = jax.lax.scan(
            step, (first, slot_cache), jnp.arange(max_new_tokens - 1))
        # toks holds tokens emitted *before* each step; append the final one
        out = jnp.concatenate(
            [prompt, jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
        return out

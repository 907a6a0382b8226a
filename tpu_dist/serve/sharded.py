"""Tensor-parallel sharded decode — serve a model too large for one chip.

The multi-rank half of `tpu_dist.serve` (the ROADMAP's "multi-rank
sharded serving behind one frontend over the role graph"): a
``model-shard`` group of W ranks holds ONE copy of the model between
them — **head-sharded attention** (each shard owns ``num_heads / W``
heads; its KV-cache pool holds only those heads' rows, no replication)
and **column/row-split MLP weights** (Megatron layout: the up-projection
column-split, the down-projection row-split, following the weight-
sharding discipline of "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training", PAPERS.md) — and decodes
cooperatively:

- every shard runs the SAME slot bookkeeping (admission order, slot
  choice, EOS/length frees) and the same per-slot ``decode_step`` /
  ``prefill_into_slot`` math locally over its weight shard;
- per transformer block, the two partial activations (attention output
  rows, MLP down-projection rows) are combined with one ring all-reduce
  each over the existing p2p data plane (``collectives/ring.py``, issued
  as async :class:`~tpu_dist.collectives.work.Work` handles on the
  ordered engine; ``comm_dtype="int8_block256"`` wire compression is an
  opt-in);
- embeddings, norms and the LM head are replicated, and the ring
  all-reduce delivers byte-identical sums to every rank — so every shard
  computes the *identical* logits and samples the *identical* next token
  (`serve.engine.sample_tokens`).  Followers therefore stay in lockstep
  WITHOUT a per-token broadcast; only the host-side *decisions* that
  depend on the leader's wall clock or request stream (admissions,
  cancel/deadline sweeps, shutdown) travel, as tiny control-plan frames.

Shard-rank 0 is the **leader**: it runs the ordinary
:class:`~tpu_dist.serve.scheduler.Scheduler` +
:class:`~tpu_dist.serve.frontend.Frontend` pair (tokens stream back
through the frontend role to the gateway), owns the
:class:`Request` objects, and broadcasts each engine operation as a plan
frame before executing it.  Ranks 1..W-1 run a :class:`ShardFollower`
loop: receive plan → mirror the operation → join the collectives.

Failure story: a SIGKILLed shard surfaces as a named
``PeerGoneError`` in whichever peer touches the ring next — the leader's
scheduler records it as the fatal cause and fails every in-flight
request BY NAME; followers get it from their blocked plan recv.  Every
rank then exits nonzero, and the supervisor's **gang** restart re-forms
the whole shard group (solo-respawning one shard is meaningless: its
peers hold the other heads of the same KV caches).

``ShardedParams.from_checkpoint`` loads a FULL checkpoint directly into
a shard's layout without materializing the full tree: each sliced leaf
is assembled from contiguous fragment range-reads out of the
uncompressed ``arrays.npz`` — the same zip-local-header fragment math
``resilience/reshard.py`` uses for elastic N→M redistribution.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..nn import cache as kvcache
from .engine import (Request, ServeError, SlotEngine, advance_rows, beside,
                     masked_rows, place_params, sample_tokens, set_row)

__all__ = ["ShardedLM", "ShardedDecoder", "ShardedSlotEngine",
           "ShardFollower", "ShardedParams", "ShardConfigError",
           "ShardPlanError", "shard_params"]

_CTL_TAG = "sctl"        # leader -> follower control-plan frames
_PLAN_TIMEOUT = 300.0    # follower's per-plan recv budget (seconds)


def _probe_interval() -> float:
    """Idle-leader liveness cadence (seconds): with no plan sent for this
    long, the leader broadcasts a ``ping`` plan so a dead follower is
    named by ``PeerGoneError`` NOW instead of by the first request that
    has to fail to discover it.  ``TPU_DIST_SERVE_PROBE`` tunes it;
    ``0`` disables the probe."""
    return float(os.environ.get("TPU_DIST_SERVE_PROBE", "") or 2.0)

# below this, the partial-sum combine takes a latency-optimal direct
# exchange (every rank sends its FULL partial to every peer, folds in
# rank order) instead of the bandwidth-optimal ring: decode partials are
# a few KB, where the ring's two sequential hops are pure latency.  The
# W*(W-1) traffic amplification is irrelevant at these sizes.
_EXCHANGE_MAX_BYTES = 128 << 10


def _tp_span(op: str, value, group: str):
    """Obs span for one tp combine, stamped with ``group=`` so
    ``obs diagnose`` attributes tensor-parallel traffic to the shard gang
    instead of the world's lockstep sequence (the body then stamps
    ``algo=`` — exchange vs ring — via note_algo)."""
    try:
        from ..obs.hooks import collective_span
    except Exception:
        import contextlib
        return contextlib.nullcontext()
    return collective_span(op, value=value, reduce_op="sum", group=group)


def _note_algo(algo: str) -> None:
    try:
        from ..obs.hooks import note_algo
        note_algo(algo)
    except Exception:
        pass


def _exchange_all_reduce(dp, arr, tag: str, timeout: float):
    """Direct-exchange SUM: one one-way latency instead of the ring's
    2(N-1) sequential hops.  Fold order is RANK order on every rank, so
    the result is byte-identical everywhere (the lockstep requirement) —
    and at world 2 it equals the ring's bytes too (a+b commutes)."""
    flat = np.ascontiguousarray(arr.reshape(-1))
    for dst in range(dp.num_processes):
        if dst != dp.rank:
            dp.send_array(dst, tag, flat)
    acc = None
    for src in range(dp.num_processes):
        part = flat if src == dp.rank else dp.recv_array(src, tag,
                                                         timeout)
        acc = part.copy() if acc is None else acc + part
    return acc.reshape(arr.shape)


class ShardConfigError(ServeError):
    """The model cannot be sharded this way (heads or MLP hidden width
    not divisible by the shard world, MoE blocks, non-causal model) —
    named at construction, before any rank allocates a cache."""


class ShardPlanError(ServeError):
    """A follower received a control plan it cannot apply (unknown op,
    slot state drift) — the shard group is no longer in lockstep and the
    only safe move is to fail the gang round loudly."""


# ---------------------------------------------------------------------------
# parameter sharding: span math shared by in-memory slicing and range-reads
# ---------------------------------------------------------------------------

# Span math now lives in the unified rule plane (parallel/rules.py):
# SERVING_RULES binds heads + MLP hidden to the shard gang, and
# spans_for() generalizes the old per-tag helpers (qkv_w/qkv_b/head_rows/
# rows/cols/vec/bias0) — golden-pinned bitwise against the pre-refactor
# layouts in tests/test_rules.py, so existing sharded checkpoints load
# unchanged.  Every span stays contiguous, which is what lets
# ShardedParams range-read them straight out of a checkpoint's
# ``arrays.npz`` (the reshard fragment discipline).


def _leaf_plan(path: str, name: str, shape: Tuple[int, ...], dims: dict,
               rank: int, world: int):
    """``(flat element spans, out_shape)`` of shard ``rank``'s slice of a
    leaf with flat C-order layout ``shape`` — or ``None`` when this shard
    drops the leaf entirely (the partial-sum bias convention: exactly one
    shard carries each row-split projection's bias, so the post-all-reduce
    sum adds it once)."""
    from ..parallel import rules as _shard_rules
    axes = {"qkv3": 3, "heads": dims["num_heads"],
            "head_dim": dims["head_dim"], "mlp": dims["hidden"],
            "embed": dims["dim"], "vocab": dims["vocab"]}
    try:
        return _shard_rules.spans_for(
            path, name, shape, axes, rank, world,
            rules=_shard_rules.SERVING_RULES, mesh_axis="shard")
    except _shard_rules.ShardLayoutError as e:
        raise ShardConfigError(str(e)) from e


def _model_dims(model) -> dict:
    """Shardable hyperparameters read off a built ``TransformerLM`` —
    raising :class:`ShardConfigError` for shapes this layout cannot
    split."""
    import jax
    attn = model.block0.attn
    if hasattr(attn, "mixers"):
        raise NotImplementedError(
            f"sharded serving divides ONE attention mixer a block by head, "
            f"and block0.attn is a {type(attn).__name__} of "
            f"{[type(m).__name__ for m in attn.mixers()]}, side by side; a "
            f"head-divided layer of several mixers is not built")
    # the per-block segments address one K/V pool entry a block
    pool = jax.eval_shape(lambda: model.init_slot_cache(1, 8))
    kvcache.require_timed(pool, "sharded serving")
    kvcache.require_heads(pool, "sharded serving")
    if getattr(model, "num_experts", 0):
        raise ShardConfigError(
            "sharded serving covers dense MLP blocks; MoE blocks are "
            "already expert-parallel (nn/moe.py) — build the model with "
            "num_experts=0")
    if not getattr(model, "causal", True):
        raise ShardConfigError("sharded decode requires a causal model")
    if getattr(model, "sequence_axis", None) is not None:
        raise ShardConfigError(
            "build the model without sequence_axis for serving (KV-cache "
            "decode runs on gathered sequences)")
    up = model.block0.mlp[0]
    return {"dim": attn.embed_dim, "num_heads": attn.num_heads,
            "head_dim": attn.head_dim, "depth": model.depth,
            "hidden": up.out_features, "vocab": model.vocab_size,
            "max_seq_len": model.max_seq_len, "rope": attn.rope,
            "rope_theta": attn.rope_theta,
            "qkv_bias": attn.bias,
            "rmsnorm": type(model.ln_f).__name__ == "RMSNorm"}


def _check_world(dims: dict, world: int) -> None:
    if world < 1:
        raise ShardConfigError(f"shard world must be >= 1, got {world}")
    if dims["num_heads"] % world:
        raise ShardConfigError(
            f"num_heads {dims['num_heads']} not divisible by shard world "
            f"{world} — the KV cache shards by head")
    if dims["hidden"] % world:
        raise ShardConfigError(
            f"MLP hidden width {dims['hidden']} not divisible by shard "
            f"world {world}")


def shard_params(model, params, shard_rank: int, shard_world: int) -> dict:
    """Slice a FULL parameter tree into shard ``shard_rank``'s layout
    (the tree a :class:`ShardedLM` of the same coordinates expects).
    Pure span math over each leaf's flat layout — identical to what
    :meth:`ShardedParams.from_checkpoint` range-reads from disk."""
    dims = _model_dims(model)
    _check_world(dims, shard_world)
    out: Dict[str, dict] = {}
    for path, leaf_dict in params.items():
        sliced = {}
        for name, arr in leaf_dict.items():
            arr = np.asarray(arr)
            plan = _leaf_plan(path, name, arr.shape, dims,
                              shard_rank, shard_world)
            if plan is None:
                continue
            spans, out_shape = plan
            flat = arr.reshape(-1)
            sliced[name] = np.concatenate(
                [flat[lo:hi] for lo, hi in spans]).reshape(out_shape)
        if sliced:
            out[path] = sliced
    return out


class ShardedParams:
    """Loader for shard-layout parameter trees."""

    @staticmethod
    def from_checkpoint(root: str, model, shard_rank: int,
                        shard_world: int, step: Optional[int] = None
                        ) -> dict:
        """Load a FULL ``tpu_dist.checkpoint`` directly into shard
        ``shard_rank``'s layout, reading only the bytes this shard will
        own (plus replicated leaves): each sliced leaf is assembled from
        contiguous fragment range-reads out of the uncompressed
        ``arrays.npz`` via the same zip-local-header parse the elastic
        reshard engine uses (``resilience/reshard._ShardReader``) — peak
        memory is one full replicated leaf, never the full tree."""
        import os

        from .. import checkpoint as ckpt
        from ..resilience.reshard import _ShardReader

        dims = _model_dims(model)
        _check_world(dims, shard_world)
        if step is None:
            step = ckpt.latest_step(root)
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {root!r}")
        step_dir = os.path.join(root, f"step_{step:08d}")
        with open(os.path.join(step_dir, "tree.json")) as f:
            doc = json.load(f)
        reader = _ShardReader.from_dir(step_dir, label="full checkpoint")
        out: Dict[str, dict] = {}
        try:
            for key, spec in doc["leaves"].items():
                m = re.match(r"^\['([^']+)'\]\['([^']+)'\]$", key)
                if m is None:
                    raise ShardConfigError(
                        f"checkpoint leaf {key!r} is not a "
                        f"{{path: {{name: array}}}} parameter tree — "
                        f"save the tree Module.init() returns")
                path, name = m.group(1), m.group(2)
                shape = tuple(spec["shape"])
                dtype = np.dtype(spec["dtype"])
                plan = _leaf_plan(path, name, shape, dims,
                                  shard_rank, shard_world)
                if plan is None:
                    continue
                spans, out_shape = plan
                parts = [reader.read_range(key, lo, hi, dtype)
                         for lo, hi in spans]
                out.setdefault(path, {})[name] = (
                    np.concatenate(parts).reshape(out_shape))
        finally:
            reader.close()
        return out


# ---------------------------------------------------------------------------
# the per-shard model: same module paths, sharded shapes
# ---------------------------------------------------------------------------


def _import_models():
    from ..models.transformer import TransformerLM
    return TransformerLM


class ShardedLM:
    """One shard's slice of a ``TransformerLM``, with the same parameter
    PATHS as the full model (``block0.attn`` …) but sharded shapes —
    ``num_heads / W`` attention heads per block, ``hidden / W`` MLP
    columns — so :func:`shard_params` trees bind directly.

    Built lazily around a full model *spec* (the hyperparameters are read
    off a constructed ``TransformerLM``; no full-size parameters are ever
    allocated — modules here are shape descriptors only).  Exposes the
    forward as per-block *segments* (``embed`` / ``attn`` / ``mlp`` /
    ``head``) because the cross-shard partial-sum all-reduces run on the
    HOST data plane, between compiled programs."""

    def __new__(cls, model, shard_rank: int, shard_world: int):
        from .. import nn
        TransformerLM = _import_models()

        dims = _model_dims(model)
        _check_world(dims, shard_world)
        if not 0 <= shard_rank < shard_world:
            raise ShardConfigError(
                f"shard_rank {shard_rank} out of range for shard world "
                f"{shard_world}")
        nl = dims["num_heads"] // shard_world
        hl = dims["hidden"] // shard_world

        # mixin FIRST: its segment-dispatch forward must shadow the full
        # model's forward in the MRO
        class _Sharded(_SegmentMixin, TransformerLM):
            pass

        self = _Sharded(
            vocab_size=dims["vocab"], dim=dims["dim"], depth=dims["depth"],
            num_heads=dims["num_heads"], max_seq_len=dims["max_seq_len"],
            causal=True, norm="rmsnorm" if dims["rmsnorm"] else "layernorm",
            rope=dims["rope"], rope_theta=dims["rope_theta"])
        # swap each block's attention + MLP for this shard's slice; the
        # attribute names stay, so parameter paths match the full model's
        for i in range(dims["depth"]):
            blk = getattr(self, f"block{i}")
            blk.attn = nn.MultiheadSelfAttention(
                nl * dims["head_dim"], nl, bias=dims["qkv_bias"],
                causal=True, rope=dims["rope"],
                rope_theta=dims["rope_theta"])
            blk.mlp = nn.Sequential(
                nn.Linear(dims["dim"], hl), nn.GELU(),
                nn.Linear(hl, dims["dim"]))
        self.shard_rank = shard_rank
        self.shard_world = shard_world
        self.shard_dims = dims
        self._assign_paths()
        return self


class _SegmentMixin:
    """The segment dispatch ``ShardedLM`` instances trace through
    ``apply`` — each ``op`` is one compiled program boundary, with the
    residual add of the PREVIOUS segment's all-reduced partial fused in
    (so the host never does float math between segments: every byte of
    the residual stream is produced by traced code identical on all
    shards)."""

    def forward(self, *args, op=None, layer=0):
        if op is None:
            raise ShardConfigError(
                "a ShardedLM holds partial weights — drive it through "
                "ShardedDecoder's segments, not a full forward")
        if op == "embed_attn":
            # embeddings fused into block 0's attention: one dispatch
            # fewer per step, and no zeros-add for the first residual
            idx, pos_offset = args
            x = self.embed_tokens(idx, pos_offset)
            blk = self.block0
            return x, blk.attn(blk.ln1(x))
        if op == "head":
            x, add = args
            return self.head(self.ln_f(x + add))
        blk = getattr(self, f"block{layer}")
        if op == "attn":
            x, add = args
            x = x + add               # previous block's reduced MLP rows
            return x, blk.attn(blk.ln1(x))
        if op == "mlp":
            x, add = args
            y = x + add               # this block's reduced attention rows
            return y, blk.mlp(blk.ln2(y))
        raise ShardConfigError(f"unknown segment op {op!r}")


# ---------------------------------------------------------------------------
# the decoder: jitted segments + ring all-reduce between them
# ---------------------------------------------------------------------------


class ShardedDecoder:
    """One shard's compiled pipeline over a :class:`ShardedLM`: per-slot
    ``decode_step`` / ``prefill_into_slot`` semantics, with each block's
    two partial activations combined by :meth:`all_reduce` (sum) over the
    group's data plane between segments.

    ``dp`` is the shard group's data plane (a
    :class:`~tpu_dist.collectives.transport.DataPlane` whose ranks are
    the shard ranks, or a sub-group view); ``dp=None`` is the degenerate
    world-1 layout (no wire, partials are totals).  ``comm_dtype``
    opts the partial-sum wire into cast or block-quantized compression
    (``"int8_block256"``): every shard still receives byte-identical
    reduced values (the quant byte-identity discipline), so the group
    stays in lockstep — but tokens may legitimately differ from the
    uncompressed decode, which is why it is an opt-in."""

    def __init__(self, model, params, dp, shard_rank: int,
                 shard_world: int, comm_dtype=None,
                 ar_timeout: float = 120.0):
        import jax
        import jax.numpy as jnp

        self.slm = (model if hasattr(model, "shard_rank")
                    else ShardedLM(model, shard_rank, shard_world))
        if (self.slm.shard_rank, self.slm.shard_world) != (shard_rank,
                                                           shard_world):
            raise ShardConfigError(
                f"ShardedLM coordinates ({self.slm.shard_rank}, "
                f"{self.slm.shard_world}) disagree with the decoder's "
                f"({shard_rank}, {shard_world})")
        # every shard, the followers too, holds its tree as its programs
        # read it (engine.place_params)
        self.params, self.placed = place_params(self.slm, params)
        self.dp = dp
        self.rank = int(shard_rank)
        self.world = int(shard_world)
        if self.world > 1 and dp is None:
            raise ShardConfigError(
                "a multi-rank shard group needs the p2p data plane "
                "(dp=None is world-1 only)")
        self.comm_dtype = comm_dtype
        self.ar_timeout = float(ar_timeout)
        self.depth = self.slm.shard_dims["depth"]
        self._seq = 0          # per-collective tag counter (lockstep)
        self._jnp = jnp
        self._layer_paths = [getattr(self.slm, f"block{i}").attn._path
                             for i in range(self.depth)]

        slm = self.slm

        def _embed_attn0(p, toks, index, entry):
            # fused embeddings + block 0 attention (state carries block
            # 0's cache; `index` is the per-slot lengths vector during
            # decode, scalar 0 during prefill — it is BOTH the position
            # offset and the cache write index)
            path = self._layer_paths[0]
            (x, part), st = slm.apply(
                p, toks, index, op="embed_attn",
                state=kvcache.call_state({path: entry}, index))
            return x, part, kvcache.split_state(st)[0][path]

        def _mk_attn(i):
            path = self._layer_paths[i]

            def f(p, x, add, entry, index):
                (x2, part), st = slm.apply(
                    p, x, add, op="attn", layer=i,
                    state=kvcache.call_state({path: entry}, index))
                return x2, part, kvcache.split_state(st)[0][path]
            return jax.jit(f, donate_argnums=(3,))

        def _mk_mlp(i):
            def f(p, x, add):
                return slm.apply(p, x, add, op="mlp", layer=i)
            return jax.jit(f)

        def _head_decode(p, x, add, temps, keys, steps, sampling):
            logits = slm.apply(p, x, add, op="head")
            return sample_tokens(logits[:, -1], temps, keys, steps,
                                 sampling)

        def _head_prefill(p, x, add, length, temp, key, sampling):
            logits = slm.apply(p, x, add, op="head")[0]     # (P, vocab)
            row = jax.lax.dynamic_index_in_dim(
                logits, jnp.asarray(length, jnp.int32) - 1, axis=0,
                keepdims=False)
            tok = sample_tokens(row[None], temp[None], key[None],
                                jnp.zeros((1,), jnp.int32), sampling)
            return tok[0]

        self._embed_attn0 = jax.jit(_embed_attn0, donate_argnums=(3,))
        self._attn = [_mk_attn(i) for i in range(self.depth)]
        self._mlp = [_mk_mlp(i) for i in range(self.depth)]
        self._head_dec = jax.jit(_head_decode, static_argnums=(6,))
        self._head_pre = jax.jit(_head_prefill, static_argnums=(6,))
        # prefill_into_slot's slot write, over this shard's head slice only
        self._write = jax.jit(kvcache.write_slot_rows, donate_argnums=(0,))

    # -- the cross-shard combine --------------------------------------------

    def all_reduce(self, partial, async_op: bool = False):
        """Sum ``partial`` across the shard group (byte-identical result
        on every shard, the lockstep requirement): small partials take
        the direct latency-optimal exchange
        (:func:`_exchange_all_reduce`), larger ones — and every
        ``comm_dtype`` config — the ring all-reduce over the data plane.
        With ``async_op=True`` returns a
        :class:`~tpu_dist.collectives.work.Work` handle on the group's
        ordered engine — errors a peer's death causes
        (``PeerGoneError``) are captured on the handle and re-raised at
        ``wait()``."""
        arr = np.asarray(partial)
        if self.world <= 1:
            if not async_op:
                return arr
            from ..collectives.work import completed_work
            return completed_work(arr, label="shard-ar")
        seq = self._seq
        self._seq += 1
        grp = f"shard:w{self.world}"
        if self.comm_dtype is None and arr.nbytes <= _EXCHANGE_MAX_BYTES:
            def run_exchange():
                with _tp_span("shard_all_reduce", arr, grp):
                    _note_algo("exchange")
                    return _exchange_all_reduce(self.dp, arr, f"sx{seq}",
                                                self.ar_timeout)
            if not async_op:
                return run_exchange()
            from ..collectives.work import engine_for
            return engine_for(self.dp).submit(run_exchange,
                                              label=f"shard-ar{seq}")
        from ..collectives.ring import ring_all_reduce
        from ..collectives.work import engine_for

        def run():
            with _tp_span("shard_all_reduce", arr, grp):
                _note_algo("ring")
                return ring_all_reduce(self.dp, arr, op="sum",
                                       tag=f"sd{seq}",
                                       comm_dtype=self.comm_dtype)
        if async_op:
            return engine_for(self.dp).submit(run, label=f"shard-ar{seq}")
        work = engine_for(self.dp).submit(run, label=f"shard-ar{seq}")
        return work.wait(self.ar_timeout)

    # -- pool operations (SlotEngine program signatures) ----------------------

    def init_slot_cache(self, slots: int, max_len: int, dtype):
        return beside(self.params,
                      self.slm.init_slot_cache(slots, max_len, dtype))

    def decode_pool(self, params, cache, tokens, lengths, temps, keys,
                    steps, sampling: bool):
        """One decode iteration over the whole pool — the sharded
        counterpart of the single-rank jitted ``_decode_fn`` (same
        signature, same return contract): two all-reduces per block,
        sampling replicated on every shard."""
        jnp = self._jnp
        lengths = jnp.asarray(lengths, jnp.int32)
        toks = jnp.asarray(tokens)[:, None]
        new_cache = dict(cache)
        p0 = self._layer_paths[0]
        x, part, new_cache[p0] = self._embed_attn0(params, toks, lengths,
                                                   cache[p0])
        for i in range(self.depth):
            if i > 0:
                path = self._layer_paths[i]
                x, part, new_cache[path] = self._attn[i](
                    params, x, add, cache[path], lengths)
            attn_out = self.all_reduce(part)
            x, part2 = self._mlp[i](params, x, attn_out)
            add = self.all_reduce(part2)
        nxt = self._head_dec(params, x, add, temps, keys, steps, sampling)
        return nxt, new_cache

    def prefill_pool(self, params, cache, prompt, length, slot, temp, key,
                     sampling: bool):
        """Prefill one request into slot ``slot`` — the sharded
        counterpart of ``_prefill_fn``: the (padded) prompt runs the
        segment pipeline at batch 1 with a fresh per-layer cache row,
        then each layer's rows are written into this shard's pool slice."""
        jnp = self._jnp
        fresh = self.slm.init_slot_cache(1, *kvcache.extent(cache))
        zero = jnp.zeros((), jnp.int32)
        rows = {}
        p0 = self._layer_paths[0]
        x, part, rows[p0] = self._embed_attn0(
            params, jnp.asarray(prompt)[None, :], zero, fresh[p0])
        for i in range(self.depth):
            if i > 0:
                path = self._layer_paths[i]
                x, part, rows[path] = self._attn[i](
                    params, x, add, fresh[path], zero)
            attn_out = self.all_reduce(part)
            x, part2 = self._mlp[i](params, x, attn_out)
            add = self.all_reduce(part2)
        tok = self._head_pre(params, x, add,
                             np.int32(length), np.float32(temp),
                             key, sampling)
        new_cache = self._write(cache, rows, np.int32(slot))
        return tok, new_cache


# ---------------------------------------------------------------------------
# leader engine + follower loop
# ---------------------------------------------------------------------------


def _plan_bytes(plan: dict) -> np.ndarray:
    return np.frombuffer(json.dumps(plan).encode(), dtype=np.uint8)


def _plan_from(arr: np.ndarray) -> dict:
    return json.loads(bytes(bytearray(np.asarray(arr, np.uint8))).decode())


class ShardedSlotEngine(SlotEngine):
    """The leader's engine (shard rank 0): every slot-bookkeeping line is
    the parent's; only the two compiled programs (decode/prefill → the
    :class:`ShardedDecoder` segment pipeline) and the three decision
    broadcast points (admission, expiry sweep, shutdown) differ.  Drive
    it from the ordinary :class:`~tpu_dist.serve.scheduler.Scheduler`.
    """

    def __init__(self, decoder: ShardedDecoder, num_slots: int = 8,
                 max_len: Optional[int] = None, cache_dtype=None,
                 min_bucket: int = 16):
        if decoder.rank != 0:
            raise ShardConfigError(
                f"the leader engine runs on shard rank 0; rank "
                f"{decoder.rank} runs a ShardFollower")
        self.decoder = decoder
        self._closed_plan_sent = False
        self._poisoned: Optional[BaseException] = None
        self._bcast_mu = threading.Lock()
        self._last_plan = time.monotonic()
        self._plan_seq = 0
        super().__init__(decoder.slm, decoder.params, num_slots=num_slots,
                         max_len=max_len, cache_dtype=cache_dtype,
                         min_bucket=min_bucket)
        self._placed = decoder.placed   # its placement, not the no-op above

    def _build_programs(self) -> None:
        dec = self.decoder

        # the engine's argument order and slot state (engine.pool_programs);
        # a sharded dense model keeps no routed-row counters, the (empty)
        # set passes through
        def _decode(params, cache, counters, slots, live, sampling):
            tokens, lengths, temps = masked_rows(slots, live)
            nxt, cache = dec.decode_pool(params, cache, tokens, lengths,
                                         temps, slots["keys"],
                                         slots["steps"], sampling)
            return nxt, cache, counters, advance_rows(slots, live, nxt)

        # a program of ONE prompt (prefill_width): the group's arrays hold
        # one row
        def _prefill(params, cache, counters, slots, prompts, lengths, into,
                     temps, keys, sampling):
            tok, cache = dec.prefill_pool(params, cache, prompts[0],
                                          lengths[0], into[0], temps[0],
                                          keys[0], sampling)
            return tok, cache, counters, set_row(slots, into[0], tok,
                                                 lengths[0], temps[0],
                                                 keys[0])

        self._decode = _decode
        self._prefill = _prefill

    # the plan of a program is on the wire before the program runs: a
    # launch stays glued to its collection, nothing is ever in flight
    settle = SlotEngine.collect_all

    # -- plan broadcast -------------------------------------------------------

    def _bcast(self, plan: dict, best_effort: bool = False) -> None:
        dec = self.decoder
        if dec.world <= 1:
            return
        # monotone plan seq rides in the frame: followers flight-record
        # it on apply, so the offline replay sanitizer can pair every
        # leader send against each follower's applied stream (a gap =
        # a missed plan frame = a desynced follower, named post-hoc)
        self._plan_seq += 1
        plan = dict(plan, seq=self._plan_seq)
        data = _plan_bytes(plan)
        for dst in range(dec.world):
            if dst == dec.rank:
                continue
            try:
                dec.dp.send_array(dst, _CTL_TAG, data)
            except Exception:
                if not best_effort:
                    raise
        from ..obs.recorder import safe_record
        safe_record("plan", "send", plan_seq=self._plan_seq,
                    plan=str(plan.get("op")), dst=dec.world - 1)
        self._last_plan = time.monotonic()

    def _pre_admit(self, req: Request, slot: int) -> None:
        self._check_lockstep()
        staged = req.staged if req.staged is not None else self.stage(req)
        self._bcast({"op": "admit", "slot": slot,
                     "prompt": np.asarray(staged).tolist(),
                     "length": int(len(req.prompt)),
                     "max_new_tokens": int(req.max_new_tokens),
                     "eos_id": req.eos_id,
                     "temperature": float(req.temperature),
                     "seed": int(req.seed)})

    @property
    def fatal_error(self):
        """The scheduler's engine-unusable probe: a poisoned lockstep is
        group-fatal even when no slot is decoding (a zombie leader would
        otherwise refuse submits by name forever instead of exiting for
        the gang restart)."""
        if self._poisoned is None:
            return None
        return ShardPlanError(
            f"shard group lost lockstep: the leader's prefill failed "
            f"AFTER its admit plan was broadcast ({self._poisoned!r}) — "
            f"followers advanced their collective sequence; the gang "
            f"must restart")

    def _check_lockstep(self) -> None:
        err = self.fatal_error
        if err is not None:
            raise err

    def prefill_width(self, bucket: int) -> int:
        """One: every admission is a plan on the wire of its own."""
        return 1

    def _admit(self, group: List[tuple]) -> None:
        try:
            super()._admit(group)
        except Exception as e:
            # the admit plan is already on the wire (the followers have
            # prefilled this slot and advanced their tag counters): a
            # per-request failure here would leave the group desynced
            # and the NEXT collective wedged for its full timeout.
            # Poison the engine — the next step() raises and the
            # scheduler fails everything by name (the gang-restart path)
            self._poisoned = e
            raise

    def launch_step(self) -> bool:
        self._check_lockstep()
        return super().launch_step()

    def _pre_step(self) -> None:
        self._bcast({"op": "step"})

    def _pre_free(self, slots: List[int]) -> None:
        self._bcast({"op": "free", "slots": [int(s) for s in slots]})

    def sweep_expired(self) -> int:
        """Parent sweep + the idle-liveness probe (PR 13's documented
        limit): the scheduler loop calls this every iteration boundary,
        so an IDLE leader still touches every follower socket on a
        bounded cadence — a SIGKILLed follower raises the named
        ``PeerGoneError`` here (the scheduler records it as fatal and the
        gang restarts) instead of wedging the first post-idle request."""
        freed = super().sweep_expired()
        self._probe_followers()
        return freed

    def _probe_followers(self) -> None:
        if self.decoder.world <= 1 or self._poisoned is not None \
                or self._closed_plan_sent:
            return
        itv = _probe_interval()
        if itv <= 0 or time.monotonic() - self._last_plan < itv:
            return
        # a follower answers a ping by merely staying connected: the
        # probe's value is the SEND walking every follower's socket,
        # where a dead peer's down marker raises by name
        self._bcast({"op": "ping"})

    def fail_all(self, exc: BaseException) -> None:
        # scheduler close / fatal: tell followers the group is done —
        # best-effort (the cause may BE a dead follower), once
        with self._bcast_mu:
            if not self._closed_plan_sent:
                self._closed_plan_sent = True
                self._bcast({"op": "close", "cause": type(exc).__name__},
                            best_effort=True)
        super().fail_all(exc)

    def close(self) -> None:
        """Idempotent clean shutdown plan (a leader exiting without a
        fatal error must still release its followers)."""
        with self._bcast_mu:
            if not self._closed_plan_sent:
                self._closed_plan_sent = True
                self._bcast({"op": "close", "cause": "shutdown"},
                            best_effort=True)


class _Shadow:
    """A follower's per-slot mirror of the leader's Request bookkeeping —
    just enough to free slots in lockstep (EOS / length)."""

    __slots__ = ("max_new_tokens", "eos_id", "emitted")

    def __init__(self, max_new_tokens: int, eos_id: Optional[int]):
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.emitted = 0


class ShardFollower:
    """Shard ranks 1..W-1: mirror the leader's engine operations from its
    control-plan frames and join every collective.  All *state* is
    derived — the sampled tokens are computed locally (identical logits →
    identical tokens), so the only wire traffic besides the partial-sum
    all-reduces is the tiny plan stream.

    :meth:`run` loops until a ``close`` plan, the leader's death
    (``PeerGoneError``), or ``deadline`` seconds; each blocked plan recv
    is bounded by ``plan_timeout``."""

    def __init__(self, decoder: ShardedDecoder, num_slots: int = 8,
                 max_len: Optional[int] = None, cache_dtype=None,
                 leader: int = 0):
        import jax.numpy as jnp

        if decoder.rank == 0:
            raise ShardConfigError(
                "shard rank 0 is the leader (ShardedSlotEngine)")
        self.decoder = decoder
        self.leader = int(leader)
        self.num_slots = int(num_slots)
        dims = decoder.slm.shard_dims
        self.max_len = int(max_len if max_len is not None
                           else dims["max_seq_len"])
        self.cache_dtype = cache_dtype or jnp.float32
        self.cache = decoder.init_slot_cache(self.num_slots, self.max_len,
                                             self.cache_dtype)
        self.lengths = np.zeros(self.num_slots, np.int32)
        self.tokens = np.zeros(self.num_slots, np.int32)
        self.temps = np.zeros(self.num_slots, np.float32)
        self.keys = np.zeros((self.num_slots, 2), np.uint32)
        self.steps_ = np.ones(self.num_slots, np.int32)
        self.active = np.zeros(self.num_slots, bool)
        self.shadow: List[Optional[_Shadow]] = [None] * self.num_slots
        self.plans_applied = 0
        self.decode_steps = 0
        self.close_cause: Optional[str] = None

    # -- plan application -----------------------------------------------------

    def _apply_admit(self, plan: dict) -> None:
        import jax

        slot = int(plan["slot"])
        if self.active[slot]:
            raise ShardPlanError(
                f"admit plan targets slot {slot} this follower still has "
                f"active — the shard group lost lockstep")
        prompt = np.asarray(plan["prompt"], np.int32)
        length = int(plan["length"])
        temp = float(plan["temperature"])
        key = np.asarray(
            jax.random.key_data(jax.random.key(int(plan["seed"]))),
            np.uint32)
        tok, self.cache = self.decoder.prefill_pool(
            self.decoder.params, self.cache, jax.device_put(prompt),
            np.int32(length), np.int32(slot), np.float32(temp), key,
            temp > 0)
        tok = int(tok)
        self.lengths[slot] = length
        self.tokens[slot] = tok
        self.temps[slot] = temp
        self.keys[slot] = key
        self.steps_[slot] = 1
        self.active[slot] = True
        sh = _Shadow(int(plan["max_new_tokens"]), plan.get("eos_id"))
        self.shadow[slot] = sh
        sh.emitted = 1
        self._maybe_free(slot, tok)

    def _apply_step(self) -> None:
        nxt, self.cache = self.decoder.decode_pool(
            self.decoder.params, self.cache, self.tokens, self.lengths,
            self.temps, self.keys, self.steps_,
            bool(np.any(self.temps > 0)))
        nxt = np.asarray(nxt)
        self.decode_steps += 1
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            tok = int(nxt[slot])
            self.lengths[slot] += 1
            self.steps_[slot] += 1
            self.tokens[slot] = tok
            self.shadow[slot].emitted += 1
            self._maybe_free(slot, tok)

    def _check_slot(self, slot) -> None:
        if not 0 <= int(slot) < self.num_slots:
            raise ShardPlanError(
                f"leader plan targets slot {slot} but this follower has "
                f"{self.num_slots} slots — leader and followers were "
                f"built with different num_slots")

    def _maybe_free(self, slot: int, token: int) -> None:
        sh = self.shadow[slot]
        if (sh.eos_id is not None and token == sh.eos_id) \
                or sh.emitted >= sh.max_new_tokens:
            self._free(slot)

    def _free(self, slot: int) -> None:
        self.active[slot] = False
        self.lengths[slot] = 0
        self.tokens[slot] = 0
        self.temps[slot] = 0.0
        self.shadow[slot] = None

    def apply_plan(self, plan: dict) -> bool:
        """Mirror one leader operation; False once the group closed."""
        op = plan.get("op")
        from ..obs.recorder import safe_record
        safe_record("plan", "apply", plan_seq=plan.get("seq"),
                    plan=str(op))
        if op == "admit":
            self._check_slot(plan["slot"])
            self._apply_admit(plan)
        elif op == "step":
            self._apply_step()
        elif op == "free":
            for slot in plan["slots"]:
                self._check_slot(slot)
                if self.shadow[int(slot)] is not None:
                    self._free(int(slot))
        elif op == "ping":
            pass    # idle-liveness probe: staying connected IS the answer
        elif op == "close":
            self.close_cause = plan.get("cause", "shutdown")
            return False
        else:
            raise ShardPlanError(f"unknown control plan op {op!r}")
        self.plans_applied += 1
        return True

    def recv_plan(self, timeout: float = _PLAN_TIMEOUT) -> dict:
        """Next control plan from the leader (FIFO); raises the data
        plane's named ``PeerGoneError`` when the leader died,
        ``TimeoutError`` after ``timeout``."""
        arr = self.decoder.dp.recv_array(self.leader, _CTL_TAG,
                                         timeout)
        return _plan_from(arr)

    def run(self, deadline: Optional[float] = None,
            plan_timeout: float = _PLAN_TIMEOUT) -> str:
        """Serve plans until close / leader death / ``deadline`` seconds.
        Returns the close cause (``"shutdown"``, the leader's fatal error
        name, or ``"deadline"``)."""
        import time
        end = None if deadline is None else time.monotonic() + deadline
        while True:
            left = plan_timeout if end is None \
                else min(plan_timeout, end - time.monotonic())
            if left <= 0:
                return "deadline"
            try:
                plan = self.recv_plan(timeout=max(left, 0.001))
            except TimeoutError:
                if end is not None and time.monotonic() >= end:
                    return "deadline"
                continue
            if not self.apply_plan(plan):
                return self.close_cause or "shutdown"

"""Pipeline parallelism (GPipe schedule) — the 'pipe' mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2c: data-parallel
only); this module exists because tpu_dist's mesh design treats pp as a
first-class axis alongside dp/tp/sp (ProcessGroup accepts arbitrary
axis_names), and the driver's multi-chip dry-run exercises it.

TPU-first design — one SPMD program, not per-stage processes:

- The transformer trunk is cut into S **stages of identical topology**
  (``depth % S == 0``), so stage parameters can be **stacked** on a leading
  axis of size S and sharded ``P('pipe')``: every device holds exactly its
  stage's weights, and the stage function is the *same* traced program on
  every device (SPMD), selected purely by the parameter shard it holds.
- The GPipe schedule is a ``lax.scan`` over ``M + S - 1`` ticks.  Each tick
  ``lax.ppermute``s the activation carry one hop down the pipe (stage i →
  i+1 over ICI), stage 0 swaps in the next microbatch's embeddings, every
  stage applies its block-stack, and the last stage's trunk outputs
  accumulate into an on-device buffer via clamped ``dynamic_update`` writes
  (early garbage writes land on slot 0 and are overwritten at tick S-1 —
  no masks in the hot loop).
- Embedding and LM head stay **replicated** (P()): each device traces the
  same embed/head compute, but gradients flow only through the copies that
  feed the pipe (embed on stage 0, head on the last stage).  The loss is
  ``psum`` of the last-stage-masked local loss, so JAX's VMA autodiff
  (see ddp.py) inserts exactly the right cross-stage gradient ``psum`` for
  the replicated leaves — stage-stacked leaves are pipe-varying and get
  **no** collective, their gradients are local by construction.
- Composes with data parallelism on a ('data', 'pipe') mesh: the batch
  shards over 'data', each data row runs an independent pipeline, and the
  same VMA autodiff inserts the gradient allreduce over 'data' because the
  loss is ``pmean``-ed over it.  The optimizer update runs inside the
  ``shard_map``, so stage parameters *and their optimizer state* stay
  sharded 1/S per device — pipeline parallelism gives ZeRO-style optimizer
  sharding of the trunk for free.

Backward through the schedule is the transpose of the scan: XLA reverses
the ``ppermute`` direction and replays ticks in reverse — the standard
bubble of (S-1)/(M + S - 1) idle ticks on both passes; raise
``num_microbatches`` to amortize it.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import nn

__all__ = ["PipelineParallel", "PipeTrainState"]


class PipeTrainState(NamedTuple):
    """State threaded through the jitted pipeline step.

    ``params`` / ``opt_state`` are two-key dicts: ``"repl"`` (embedding +
    head, replicated) and ``"stages"`` (trunk blocks stacked on a leading
    stage axis, sharded ``P('pipe')``)."""
    params: Any
    opt_state: Any
    step: jnp.ndarray


class _Embed(nn.Module):
    """Token + learned positional embeddings (the model's own modules, so
    parameter pytrees transfer 1:1 between pipeline and plain layouts).
    ``pos`` is None for rope models — positions then enter through the
    attention rotations inside the stages."""

    def __init__(self, tok, pos):
        super().__init__()
        self.tok = tok
        self.pos = pos

    def forward(self, idx):
        if self.pos is None:
            return self.tok(idx)
        t = idx.shape[1]
        return self.tok(idx) + self.pos(jnp.arange(t))


class _Head(nn.Module):
    """Final LayerNorm + LM head."""

    def __init__(self, ln_f, head):
        super().__init__()
        self.ln_f = ln_f
        self.head = head

    def forward(self, x):
        return self.head(self.ln_f(x))


class PipelineParallel:
    """GPipe-parallel training driver for :class:`~tpu_dist.models.TransformerLM`.

    Usage::

        pg = dist.init_process_group(axis_names=("pipe",))   # or (data, pipe)
        pp = PipelineParallel(model, optimizer=optim.AdamW(3e-4),
                              loss_fn=nn.CrossEntropyLoss(), group=pg,
                              num_microbatches=8)
        state = pp.init(seed=0)
        state, metrics = pp.train_step(state, tokens, targets)

    ``tokens``/``targets`` are ``(B, T)`` int arrays; ``B`` must divide by
    ``num_microbatches`` (and by the data-axis size when present).
    """

    def __init__(self, model, optimizer=None, loss_fn=None, group=None,
                 num_microbatches: Optional[int] = None,
                 pipe_axis: str = "pipe", data_axis: Optional[str] = None,
                 donate: bool = True, compute_dtype=None,
                 schedule: str = "gpipe"):
        """``compute_dtype``: run forward/backward (and the inter-stage
        ppermute traffic) in this dtype — bf16 halves the ICI bytes per
        hop and keeps the MXU on its fast path — while parameters,
        gradients, and optimizer state stay float32 master copies (same
        mixed-precision recipe as the DDP wrapper's ``compute_dtype``).

        ``schedule``: ``"gpipe"`` (all-forward-then-all-backward via
        autodiff of the tick scan) or ``"1f1b"`` (one-forward-one-backward
        — a hand-scheduled scan interleaving each microbatch's backward
        with later microbatches' forwards, see _build_1f1b_step).  Same
        math, same bubble fraction; 1F1B bounds the stashed activations
        at ``min(2S-1, M)`` microbatch inputs per device instead of the
        autodiff scan's ``M+S-1`` saved ticks — the standard memory
        argument for 1F1B, here with recompute-based stage backward (the
        memory regime GPipe needs ``remat=True`` to reach)."""
        if group is None:
            from .. import dist as _dist
            group = _dist.get_default_group()
        if pipe_axis not in group.mesh.axis_names:
            raise ValueError(f"mesh {group.mesh.axis_names} has no "
                             f"{pipe_axis!r} axis")
        if data_axis is None and len(group.mesh.axis_names) > 1:
            others = [a for a in group.mesh.axis_names if a != pipe_axis]
            if len(others) == 1:
                data_axis = others[0]
            else:
                raise ValueError("pass data_axis= explicitly on a >2-D mesh")
        if getattr(model, "sequence_axis", None) is not None:
            raise ValueError("pipeline parallelism microbatches over the "
                             "batch dim; build the model without "
                             "sequence_axis (pp x sp needs a 3-D mesh recipe)")
        if (getattr(model, "embedding_multiplier", 1.0) != 1.0
                or getattr(model, "head_multiplier", 1.0) != 1.0):
            raise NotImplementedError(
                "the pipeline's embedding and head stages apply no "
                "embedding_multiplier / head_multiplier, and this model "
                "has them")
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"schedule must be 'gpipe' or '1f1b', "
                             f"got {schedule!r}")
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.group = group
        self.pipe_axis = pipe_axis
        self.data_axis = data_axis
        self.donate = donate
        self.compute_dtype = compute_dtype
        self.schedule = schedule
        self.num_stages = group.mesh.shape[pipe_axis]
        if model.depth % self.num_stages:
            raise ValueError(f"depth {model.depth} not divisible by "
                             f"{self.num_stages} pipeline stages")
        self.blocks_per_stage = model.depth // self.num_stages
        self.num_microbatches = num_microbatches or self.num_stages
        # canonical stage program: the first blocks_per_stage blocks.  Module
        # objects hold topology only (nn/module.py design), so one stage's
        # module tree serves as the traced program for every stage — which
        # weights it runs with is decided by the P('pipe') parameter shard.
        self._stage = nn.Sequential(*[getattr(model, f"block{i}")
                                      for i in range(self.blocks_per_stage)])
        self._embed = _Embed(model.tok, model.pos)
        self._head = _Head(model.ln_f, model.head)
        self._canon_paths = None  # stage-relative -> block0-rooted paths
        self._train_step = None

    # -- parameter layout ------------------------------------------------------

    def _stage_paths(self):
        """Canonical stage-relative leaf paths ("0.ln1", "1.mlp.0", ...) and
        their block-index/suffix decomposition."""
        if self._canon_paths is None:
            self._stage._assign_paths()
            paths = []
            for path, mod in self._stage.named_modules():
                if type(mod).create_params is not nn.Module.create_params:
                    j, _, suffix = path.partition(".")
                    paths.append((path, int(j), suffix))
            self._canon_paths = paths
        return self._canon_paths

    def pack_params(self, model_params):
        """Plain ``model.init()`` pytree → pipeline layout ``{"repl",
        "stages"}`` (stage leaves stacked on a leading S axis)."""
        s, k = self.num_stages, self.blocks_per_stage
        stages = {}
        for canon, j, suffix in self._stage_paths():
            def src(stage):
                base = f"block{stage * k + j}"
                return model_params[f"{base}.{suffix}" if suffix else base]
            names = src(0).keys()
            stages[canon] = {n: jnp.stack([src(st)[n] for st in range(s)])
                            for n in names}
        embed = {"tok": model_params["tok"]}
        if "pos" in model_params:
            embed["pos"] = model_params["pos"]
        repl = {"embed": embed,
                "head": {"ln_f": model_params["ln_f"],
                         "head": model_params["head"]}}
        return {"repl": repl, "stages": stages}

    def unpack_params(self, pipe_params):
        """Inverse of :meth:`pack_params` — e.g. to checkpoint in the plain
        layout or hand weights to an unsharded model for decoding."""
        k = self.blocks_per_stage
        out = {"tok": pipe_params["repl"]["embed"]["tok"],
               "ln_f": pipe_params["repl"]["head"]["ln_f"],
               "head": pipe_params["repl"]["head"]["head"]}
        if "pos" in pipe_params["repl"]["embed"]:
            out["pos"] = pipe_params["repl"]["embed"]["pos"]
        for canon, j, suffix in self._stage_paths():
            stacked = pipe_params["stages"][canon]
            for st in range(self.num_stages):
                base = f"block{st * k + j}"
                path = f"{base}.{suffix}" if suffix else base
                out[path] = {n: v[st] for n, v in stacked.items()}
        return out

    def _param_specs(self, params):
        """PartitionSpec pytree: stages P('pipe') on the stacked axis,
        everything else replicated."""
        pipe = self.pipe_axis
        return {"repl": jax.tree.map(lambda _: P(), params["repl"]),
                "stages": jax.tree.map(lambda _: P(pipe), params["stages"])}

    def _opt_specs(self, opt_state):
        """Optimizer-state specs: leaves mirroring stacked stage params keep
        P('pipe'); scalars (step counters) replicate."""
        pipe = self.pipe_axis

        def split(sub, stacked):
            return jax.tree.map(
                lambda l: P(pipe) if (stacked and getattr(l, "ndim", 0) >= 1)
                else P(), sub)

        return {"repl": split(opt_state["repl"], False),
                "stages": split(opt_state["stages"], True)}

    # -- state -----------------------------------------------------------------

    def init(self, seed: int = 0) -> PipeTrainState:
        """Deterministic state build: plain ``model.init`` then repack, so
        pipeline training starts from bit-identical weights to a
        single-device run with the same seed."""
        params = self.pack_params(self.model.init(jax.random.key(seed)))
        if self.optimizer is None:
            opt_state = {"repl": {}, "stages": {}}
        else:
            opt_state = {"repl": self.optimizer.init(params["repl"]),
                         "stages": self.optimizer.init(params["stages"])}
        state = PipeTrainState(params, opt_state, jnp.zeros((), jnp.int32))
        return jax.tree.map(jax.device_put, state, self.state_shardings(state))

    def state_shardings(self, state: PipeTrainState) -> PipeTrainState:
        """NamedSharding pytree mirroring ``state``'s placement (for
        ``tpu_dist.checkpoint.restore(sharding=...)``)."""
        mesh = self.group.mesh
        spec = PipeTrainState(self._param_specs(state.params),
                              self._opt_specs(state.opt_state), P())
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), spec,
                            is_leaf=lambda x: isinstance(x, P))

    # -- compiled step ---------------------------------------------------------

    def _build_train_step(self):
        stage, embed, head = self._stage, self._embed, self._head
        loss_fn, optimizer = self.loss_fn, self.optimizer
        pipe, data = self.pipe_axis, self.data_axis
        s, m = self.num_stages, self.num_microbatches
        vocab = self.model.vocab_size
        cdtype = self.compute_dtype

        def cast(tree):
            if cdtype is None:
                return tree
            return jax.tree.map(
                lambda v: v.astype(cdtype)
                if jnp.issubdtype(v.dtype, jnp.floating) else v, tree)

        def local_step(state: PipeTrainState, x, y):
            params, opt_state, step = state
            idx = lax.axis_index(pipe)
            is_last = idx == s - 1
            b_loc, t = x.shape
            mb = b_loc // m
            x_mb = x.reshape(m, mb, t)

            def trunk(repl_p, stages_p, x_mb):
                """GPipe loop → last-stage trunk outputs (m, mb, t, d)."""
                stage_local = jax.tree.map(lambda v: v[0], stages_p)
                perm = [(i, (i + 1) % s) for i in range(s)]

                def tick(carry, tick_t):
                    h, out = carry
                    prev = lax.ppermute(h, pipe, perm)
                    inj = embed.apply(repl_p["embed"],
                                      x_mb[jnp.minimum(tick_t, m - 1)])
                    h = jnp.where(idx == 0, inj, prev)
                    if self.model.remat:
                        # honor the model's per-block remat policy: recompute
                        # the stage's activations during backward instead of
                        # holding every tick's intermediates across the scan
                        h = jax.checkpoint(stage.apply)(stage_local, h)
                    else:
                        h = stage.apply(stage_local, h)
                    # clamped write: ticks < s-1 land on slot 0 and are
                    # overwritten at tick s-1, so no validity mask is needed
                    slot = jnp.clip(tick_t - (s - 1), 0, m - 1)
                    out = lax.dynamic_update_index_in_dim(out, h, slot, 0)
                    return (h, out), None

                dim = self.model.tok.embedding_dim
                # the carry crosses stages (ppermute), mixes with the
                # pipe-varying stage index, and holds data-sharded
                # activations — it must start varying over every mesh axis
                # the tick output is varying over, or scan rejects the body
                axes = (pipe,) if data is None else (data, pipe)
                adtype = cdtype or jnp.float32
                h0 = jnp.zeros(x_mb.shape[1:] + (dim,), adtype)
                out0 = jnp.zeros((m,) + h0.shape, adtype)
                for ax in axes:
                    h0 = lax.pcast(h0, ax, to="varying")
                    out0 = lax.pcast(out0, ax, to="varying")
                (_, out), _ = lax.scan(tick, (h0, out0), jnp.arange(m + s - 1))
                return out

            def loss_of(p):
                # the cast is differentiable: bf16 compute, f32 master
                # params/grads (cotangents cast back on the way out)
                p = cast(p)
                out = trunk(p["repl"], p["stages"], x_mb)
                logits = head.apply(p["repl"]["head"],
                                    out.reshape(b_loc, t, -1))
                local = loss_fn(logits.reshape(-1, vocab), y.reshape(-1))
                correct = (logits.argmax(-1) == y).sum()
                # only the last stage's buffer holds the real trunk output;
                # psum of the masked loss broadcasts it pipe-invariant, and
                # its VMA transpose routes gradient only into that copy
                loss = lax.psum(jnp.where(is_last, local, 0.0), pipe)
                correct = lax.psum(jnp.where(is_last, correct, 0), pipe)
                if data is not None:
                    loss = lax.pmean(loss, data)
                    correct = lax.psum(correct, data)
                return loss, correct

            (loss, correct), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)

            new_repl, opt_repl = optimizer.update(
                grads["repl"], opt_state["repl"], params["repl"])
            new_stages, opt_stages = optimizer.update(
                grads["stages"], opt_state["stages"], params["stages"])
            new_params = {"repl": new_repl, "stages": new_stages}
            new_opt = {"repl": opt_repl, "stages": opt_stages}

            new_state = PipeTrainState(new_params, new_opt, step + 1)
            return new_state, {"loss": loss, "correct": correct}

        def specs_of(state):
            return PipeTrainState(self._param_specs(state.params),
                                  self._opt_specs(state.opt_state), P())

        def build(state):
            state_spec = specs_of(state)
            batch_spec = P(data) if data is not None else P()
            fn = jax.shard_map(local_step, mesh=self.group.mesh,
                               in_specs=(state_spec, batch_spec, batch_spec),
                               out_specs=(state_spec, P()))
            return jax.jit(fn, donate_argnums=(0,) if self.donate else ())

        return build

    def _build_1f1b_step(self):
        """One-forward-one-backward schedule, hand-written (autodiff of the
        GPipe scan cannot interleave passes — the backward IS the scan's
        transpose).  One ``lax.scan`` over ``2S + M - 1`` ticks; at tick t,
        the device holding stage ``i``:

        - **forward unit**: runs microbatch ``f = t - i`` through its block
          stack (activations arrive by forward ``ppermute``; stage 0
          injects embeddings), stashing the stage INPUT in an
          ``K = min(2S-1, M)``-slot ring — the 1F1B in-flight bound.  The
          last stage immediately runs head + loss and their VJP, parking
          the trunk-output cotangent in a 2-slot ring (it is consumed one
          tick later) and banking the head gradients;
        - **backward unit**: runs the VJP of its stage for microbatch
          ``j = t - (2S - 1 - i)`` — the incoming cotangent is the
          reverse-``ppermute``d carry (or the parked head cotangent at the
          last stage), the stage input is popped from the ring and the
          forward RECOMPUTED inside ``jax.vjp`` (remat-style backward, so
          nothing beyond the ring is ever stored); stage 0 routes the
          resulting input cotangent through the embedding VJP.

        Gradients accumulate in f32 buffers in the carry; after the scan
        they get the collectives VMA autodiff inserted for GPipe: psum
        over 'pipe' for the replicated embed/head leaves (each is nonzero
        on one stage only), pmean over 'data' for everything.  Losses and
        correct-counts bank at the last stage's forward unit.
        """
        stage, embed, head = self._stage, self._embed, self._head
        loss_fn, optimizer = self.loss_fn, self.optimizer
        pipe, data = self.pipe_axis, self.data_axis
        s, m = self.num_stages, self.num_microbatches
        vocab = self.model.vocab_size
        cdtype = self.compute_dtype
        k_slots = min(2 * s - 1, m)

        def cast(tree):
            if cdtype is None:
                return tree
            return jax.tree.map(
                lambda v: v.astype(cdtype)
                if jnp.issubdtype(v.dtype, jnp.floating) else v, tree)

        def local_step(state: PipeTrainState, x, y):
            params, opt_state, step = state
            idx = lax.axis_index(pipe)
            is_first = idx == 0
            is_last = idx == s - 1
            b_loc, t_len = x.shape
            mb = b_loc // m
            x_mb = x.reshape(m, mb, t_len)
            y_mb = y.reshape(m, mb, t_len)
            dim = self.model.tok.embedding_dim
            adtype = cdtype or jnp.float32

            # CRITICAL: every params pytree fed to a jax.vjp below must be
            # device-VARYING on every mesh axis first.  Inside shard_map,
            # vjp w.r.t. a mesh-INVARIANT input auto-inserts a psum of the
            # per-device cotangents (the VMA autodiff rule the GPipe path
            # exploits on purpose) — which here would mix other stages'
            # masked-out garbage head/embed gradients in BEFORE our bank
            # masks can drop them (measured: ~3x-wrong repl grads).  With
            # varying inputs the vjps stay collective-free and the
            # explicit psums/pmeans after the scan do the reductions.
            axes = (pipe,) if data is None else (data, pipe)

            def vary(tree, over):
                def one(v):
                    for ax in over:
                        v = lax.pcast(v, ax, to="varying")
                    return v
                return jax.tree.map(one, tree)

            cparams = cast(params)
            # stage shards are already pipe-varying; repl leaves are
            # invariant on every axis
            stage_local = vary(jax.tree.map(lambda v: v[0],
                                            cparams["stages"]),
                               () if data is None else (data,))
            repl_embed = vary(cparams["repl"]["embed"], axes)
            repl_head = vary(cparams["repl"]["head"], axes)

            fwd_perm = [(i, (i + 1) % s) for i in range(s)]
            bwd_perm = [(i, (i - 1) % s) for i in range(s)]

            def stage_fn(sp, h):
                return stage.apply(sp, h)

            def head_loss(hp, out, y_j):
                logits = head.apply(hp, out)
                loss = loss_fn(logits.reshape(-1, vocab), y_j.reshape(-1))
                correct = (logits.argmax(-1) == y_j).sum()
                return loss, correct

            def tick(carry, tick_t):
                (h, g, stash, cot_ring, g_stage, g_embed, g_head,
                 loss_sum, correct_sum) = carry

                # backward-unit READS of the rings happen before the
                # forward unit writes them: at stage 0 the microbatch
                # being stashed and the one being back-propagated can
                # collide on a slot in the same tick (f - j = 2S-1-2i)
                j = tick_t - (2 * s - 1 - idx)
                bwd_on = (j >= 0) & (j < m)
                j_c = jnp.clip(j, 0, m - 1)
                h_saved = lax.dynamic_index_in_dim(stash, j_c % k_slots, 0,
                                                   keepdims=False)
                parked = lax.dynamic_index_in_dim(cot_ring, j_c % 2, 0,
                                                  keepdims=False)

                # ---- forward unit -----------------------------------
                f = tick_t - idx
                fwd_on = (f >= 0) & (f < m)
                f_c = jnp.clip(f, 0, m - 1)
                prev = lax.ppermute(h, pipe, fwd_perm)
                inj = embed.apply(repl_embed, x_mb[f_c]).astype(adtype)
                h_in = jnp.where(is_first, inj, prev)
                h_out = stage_fn(stage_local, h_in)
                h_new = h_out
                # ring write, masked against clobbering a live slot when
                # this tick's forward is idle (warmup/drain)
                slot = f_c % k_slots
                old_slot = lax.dynamic_index_in_dim(stash, slot, 0,
                                                    keepdims=False)
                stash = lax.dynamic_update_index_in_dim(
                    stash, jnp.where(fwd_on, h_in, old_slot), slot, 0)

                # last stage: head + loss VJP on the fresh trunk output;
                # the cotangent is consumed by the backward unit next tick
                (loss_f, hl_vjp, correct_f) = jax.vjp(
                    lambda hp, out: head_loss(hp, out, y_mb[f_c]),
                    repl_head, h_out, has_aux=True)
                # the seed must carry loss_f's varying-mesh-axes type
                # (a fresh constant is mesh-invariant and vjp rejects it)
                d_head, d_out = hl_vjp(loss_f * 0 + 1)
                bank = fwd_on & is_last
                g_head = jax.tree.map(
                    lambda a, d: a + jnp.where(bank, 1.0, 0.0)
                    * d.astype(jnp.float32), g_head, d_head)
                loss_sum = loss_sum + jnp.where(bank, loss_f, 0.0)
                correct_sum = correct_sum + jnp.where(bank, correct_f, 0)
                cslot = f_c % 2
                old_c = lax.dynamic_index_in_dim(cot_ring, cslot, 0,
                                                 keepdims=False)
                cot_ring = lax.dynamic_update_index_in_dim(
                    cot_ring, jnp.where(bank, d_out.astype(adtype), old_c),
                    cslot, 0)

                # ---- backward unit ----------------------------------
                g_prev = lax.ppermute(g, pipe, bwd_perm)
                g_in = jnp.where(is_last, parked, g_prev)
                _, st_vjp = jax.vjp(stage_fn, stage_local, h_saved)
                d_stage, d_h = st_vjp(g_in.astype(adtype))
                live = jnp.where(bwd_on, 1.0, 0.0)
                g_stage = jax.tree.map(
                    lambda a, d: a + live * d.astype(jnp.float32),
                    g_stage, d_stage)
                # stage 0: the input cotangent belongs to the embeddings
                _, em_vjp = jax.vjp(
                    lambda ep: embed.apply(ep, x_mb[j_c]).astype(adtype),
                    repl_embed)
                (d_embed,) = em_vjp(d_h)
                g_embed = jax.tree.map(
                    lambda a, d: a + jnp.where(bwd_on & is_first, 1.0, 0.0)
                    * d.astype(jnp.float32), g_embed, d_embed)
                g_new = d_h

                return (h_new, g_new, stash, cot_ring, g_stage, g_embed,
                        g_head, loss_sum, correct_sum), None

            # carries start varying over every mesh axis the tick outputs
            # vary over (same requirement as the GPipe trunk scan)
            def varying(a):
                for ax in axes:
                    a = lax.pcast(a, ax, to="varying")
                return a

            h0 = varying(jnp.zeros((mb, t_len, dim), adtype))
            g0 = varying(jnp.zeros((mb, t_len, dim), adtype))
            stash0 = varying(jnp.zeros((k_slots, mb, t_len, dim), adtype))
            cot0 = varying(jnp.zeros((2, mb, t_len, dim), adtype))
            zeros_f32 = lambda tree: jax.tree.map(
                lambda v: varying(jnp.zeros(v.shape, jnp.float32)), tree)
            carry0 = (h0, g0, stash0, cot0, zeros_f32(stage_local),
                      zeros_f32(repl_embed), zeros_f32(repl_head),
                      varying(jnp.zeros((), jnp.float32)),
                      varying(jnp.zeros((), jnp.int32)))
            (_, _, _, _, g_stage, g_embed, g_head, loss_sum,
             correct_sum), _ = lax.scan(tick, carry0,
                                        jnp.arange(2 * s + m - 1))

            # collectives the GPipe path gets from VMA autodiff: repl
            # grads live on one stage each -> psum over pipe; everything
            # averages over data; per-token loss normalizes by microbatch
            # count (loss_fn averages within a microbatch)
            loss = lax.psum(loss_sum, pipe) / m
            correct = lax.psum(correct_sum, pipe)
            g_embed = lax.psum(g_embed, pipe)
            g_head = lax.psum(g_head, pipe)
            g_stage = jax.tree.map(lambda v: v / m, g_stage)
            g_embed = jax.tree.map(lambda v: v / m, g_embed)
            g_head = jax.tree.map(lambda v: v / m, g_head)
            if data is not None:
                loss = lax.pmean(loss, data)
                correct = lax.psum(correct, data)
                g_stage = jax.tree.map(lambda v: lax.pmean(v, data), g_stage)
                g_embed = jax.tree.map(lambda v: lax.pmean(v, data), g_embed)
                g_head = jax.tree.map(lambda v: lax.pmean(v, data), g_head)

            # back to the {"repl", "stages"} layout: stage grads gain the
            # leading stage axis (this device's slice), repl grads merge
            grads = {
                "repl": {"embed": g_embed, "head": g_head},
                "stages": jax.tree.map(lambda v: v[None].astype(jnp.float32),
                                       g_stage),
            }
            grads = jax.tree.map(lambda g_, p_: g_.astype(p_.dtype),
                                 grads, params)

            new_repl, opt_repl = optimizer.update(
                grads["repl"], opt_state["repl"], params["repl"])
            new_stages, opt_stages = optimizer.update(
                grads["stages"], opt_state["stages"], params["stages"])
            new_state = PipeTrainState(
                {"repl": new_repl, "stages": new_stages},
                {"repl": opt_repl, "stages": opt_stages}, step + 1)
            return new_state, {"loss": loss, "correct": correct}

        def build(state):
            state_spec = PipeTrainState(self._param_specs(state.params),
                                        self._opt_specs(state.opt_state),
                                        P())
            batch_spec = P(data) if data is not None else P()
            fn = jax.shard_map(local_step, mesh=self.group.mesh,
                               in_specs=(state_spec, batch_spec, batch_spec),
                               out_specs=(state_spec, P()))
            return jax.jit(fn, donate_argnums=(0,) if self.donate else ())

        return build

    def train_step(self, state: PipeTrainState, x, y):
        """One fused pipeline step (all S stages, all M microbatches, grads,
        update) → ``(new_state, {"loss", "correct"})``."""
        if self.optimizer is None or self.loss_fn is None:
            raise ValueError("train_step requires optimizer= and loss_fn=")
        if self._train_step is None:
            build = (self._build_1f1b_step() if self.schedule == "1f1b"
                     else self._build_train_step())
            self._train_step = build(state)
        return self._train_step(state, x, y)

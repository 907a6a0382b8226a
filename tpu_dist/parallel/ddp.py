"""DistributedDataParallel — the L3 wrapper, compiled instead of hooked.

torch's DDP (`/root/reference/mpspawn_dist.py:68`, `example_mp.py:53`) is a
*runtime object*: it broadcasts parameters from rank 0 at wrap time, then
hooks autograd to fire bucketed NCCL all-reduces overlapped with backward.

On TPU none of that machinery exists at runtime — it is **compiled in**
(SURVEY.md §7 design stance; BASELINE.json north star: "fwd/bwd + gradient
all-reduce in a single XLA graph").  This wrapper builds ONE jitted step:

    forward → loss → pmean(loss) over the data axis → grad → SGD update

under ``shard_map`` over the group's mesh.  Two properties make the gradient
all-reduce both correct and free:

- **JAX 0.9 VMA autodiff**: inside ``shard_map``, parameters enter replicated
  (``P()`` in_spec).  Differentiating w.r.t. a replicated value auto-inserts
  the ``psum`` of per-device cotangents.  Taking the gradient *of the
  pmean-ed loss* therefore yields exactly the DDP-averaged gradient — adding
  an explicit ``pmean`` on grads afterwards would double-count (verified the
  hard way; see .claude/skills/verify/SKILL.md).
- **XLA fusion/scheduling**: the all-reduce is an op in the backward graph,
  so XLA overlaps it with remaining backward compute on ICI — the same
  overlap DDP's Reducer implements by hand with buckets and streams.

Over a group of more than one the weight update is sharded leaf by leaf
(Xu et al., arXiv:2004.13336): a replica HOLDS 1/world of each divided
parameter leaf and of its optimizer state along :func:`shard_axis`; a step
all-gathers the parameters for the forward and backward passes,
reduce-scatters each gradient leaf and updates the 1/world it holds — the
all-reduce's bytes, 1/world of the update
(``shard_optimizer``, :meth:`DistributedDataParallel.update_plan`).

BatchNorm semantics (SURVEY.md §2b #16): batch statistics stay **per-replica**
(DDP parity — torch DDP does not sync BN).  Running-stat *updates* are
pmean-ed across replicas to keep the state replicated; this is a documented,
deliberate improvement over torch's keep-rank-0's-stats (identical in
distribution, strictly less variance).  ``sync_batchnorm=True`` converts BN
layers to cross-replica batch stats (torch SyncBatchNorm parity).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
# jax 0.9.0 keeps the varying -> invariant all-gather out of ``jax.lax``;
# under ``shard_map`` it type-checks as replicated and lowers to ONE
# all-gather (``lax.all_gather`` leaves its result marked varying)
from jax._src.lax.parallel import all_gather_invariant

from ..nn.layers import BatchNorm2d
from ..nn.module import Module
from ..obs.spans import span

__all__ = ["TrainState", "DistributedDataParallel", "convert_sync_batchnorm",
           "shard_axis"]

# A leaf under this many elements keeps the whole update on every replica:
# a quarter of a LayerNorm vector or a bias is not worth a reduce-scatter
# and an all-gather of its own.  GPT-2 medium's 195 vectors (1,024-50,257
# elements, 0.09% of the model) lie below it, its 99 matrices (>= 2**20)
# above; PERF.md section 3 counts both sides.
SHARD_MIN_ELEMENTS = 1 << 16


class TrainState(NamedTuple):
    """Training state threaded through the jitted step.  Replicated over the
    group — except, where the weight update is sharded (a group of more than
    one by default), the divided leaves of ``params`` and of ``opt_state``:
    each is held 1/world per device along :func:`shard_axis`.  They are
    ordinary global arrays all the same: reading one on the host, or handing
    it to a function that wants it replicated, gathers it."""
    params: Any
    model_state: Any      # BN running stats etc.; {} for stateless nets
    opt_state: Any
    step: jnp.ndarray     # scalar int32
    rng: jnp.ndarray      # base PRNG key; per-step/per-replica keys derive


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def shard_axis(shape, n: int) -> Optional[int]:
    """The axis along which a group of ``n`` divides a parameter leaf of
    this shape for the sharded weight update: the first one ``n`` divides.
    None where the leaf stays whole: a group of one, no such axis (GPT-2's
    ``[50257]`` head bias), or under :data:`SHARD_MIN_ELEMENTS`.  A pure
    function of ``(shape, n)``: a moment has its parameter's shape, so the
    parameter, its gradient and its optimizer state agree on the axis."""
    if n <= 1 or math.prod(shape) < SHARD_MIN_ELEMENTS:
        return None
    return next((d for d, s in enumerate(shape) if s % n == 0), None)


def _zero1_spec(leaf, axis: str, n: int) -> P:
    """Placement of a parameter or optimizer-state leaf under the sharded
    update: held 1/world along its :func:`shard_axis`; scalar leaves
    (schedule/Adam step counters) and the leaves that stay whole replicate.
    Single source of truth for state_shardings() and the train-step
    in/out_specs — they must agree or restore-time placement breaks."""
    d = shard_axis(getattr(leaf, "shape", ()), n)
    return P() if d is None else P(*([None] * d), axis)


def convert_sync_batchnorm(module: Module, axis_name: str) -> Module:
    """Set every BatchNorm layer to compute cross-replica batch statistics
    (torch ``SyncBatchNorm.convert_sync_batchnorm`` parity).  Mutates and
    returns the module (topology objects hold no arrays, so this is safe
    before ``init``/``apply``)."""
    for _, m in module.named_modules():
        if isinstance(m, BatchNorm2d):
            m.axis_name = axis_name
    return module


class DistributedDataParallel:
    """Data-parallel training driver over a process group's mesh.

    Usage (the reference loop shape, /root/reference/mpspawn_dist.py:97-118)::

        pg = dist.init_process_group()
        ddp = DistributedDataParallel(model, optimizer=SGD(lr),
                                      loss_fn=nn.CrossEntropyLoss(), group=pg)
        state = ddp.init(seed=0)        # == manual_seed(0) on every rank
        for epoch in range(E):
            loader.set_epoch(epoch)
            for xb, yb in device_loader:
                state, metrics = ddp.train_step(state, xb, yb)

    ``metrics`` holds ``loss`` (global mean) and ``correct`` (global count),
    as on-device scalars — don't block on them every step (SURVEY.md §7:
    ``loss.item()`` per step kills pipelining; log every N).
    """

    def __init__(self, module: Module, optimizer=None, loss_fn=None,
                 group=None, sync_batchnorm: bool = False,
                 donate: bool = True, compute_dtype=None,
                 accum_steps: int = 1,
                 shard_optimizer: Optional[bool] = None, comm_dtype=None):
        """Options beyond torch-DDP parity:

        ``compute_dtype``: run forward/backward in this dtype (bf16 for the
        MXU) while parameters, gradients and optimizer state stay float32
        master copies — the mixed-precision recipe of BASELINE.md ladder #4.

        ``accum_steps``: split each incoming batch into k microbatches,
        accumulate gradients locally, and all-reduce ONCE per step — the
        comms pattern of torch DDP's ``no_sync`` accumulation, compiled as a
        ``lax.scan``.

        ``shard_optimizer``: cross-replica sharding of the weight update
        (ZeRO-1; Xu et al., arXiv:2004.13336 — the XLA data-parallel
        paper), leaf by leaf.  A replica holds 1/world of each divided
        parameter leaf and of its optimizer state along the leaf's
        :func:`shard_axis`; a step all-gathers the parameters for its
        forward and backward passes, reduce-scatters each gradient leaf,
        and updates the 1/world it holds — the all-reduce's bytes on the
        wire, a 1/world of the update's work and of the parameters' and
        moments' memory at rest.  (The gather opens the step rather than
        closing it: a gathered parameter that is a program's RESULT costs a
        copy of the whole leaf on the TPU, twice with donation; one that
        feeds the cast to ``compute_dtype`` costs nothing more.)  Leaves
        with no axis the group size divides, or under
        :data:`SHARD_MIN_ELEMENTS`, stay replicated and keep the all-reduce
        and the whole update.  ``None`` (the default) decides by group
        size: sharded when the group has more than one member; ``False``
        keeps every leaf replicated and every update whole (the oracle the
        sharded path is tested against); ``True`` is the same as ``None``
        (a group of one has nothing to shard).  Numerics identical to the
        whole update (tested): every optimizer in :mod:`tpu_dist.optim` is
        elementwise per leaf plus scalar counters.  :meth:`update_plan`
        says what was sharded; :meth:`eval_step` and :meth:`forward` take
        the held parameters as they are (one gather a call); restore a
        checkpoint through :meth:`state_shardings`.

        ``comm_dtype``: compress the gradient all-reduce to this dtype
        (torch DDP *comm hook* parity — ``fp16_compress_hook`` /
        ``bf16_compress_hook``): local grads are divided by world size,
        cast to ``comm_dtype`` for the wire (pre-division keeps the fp16
        sum under 65504 at any world size, as the torch hook does), summed,
        and cast back to the gradient's dtype before the optimizer update.
        Halves ICI/DCN bytes per step with 16-bit dtypes; composes with
        ``accum_steps`` (compression happens once, at sync time, like the
        torch hook) and the sharded update (the reduce-scatter runs
        compressed).
        """
        if group is None:
            from .. import dist as _dist
            group = _dist.get_default_group()
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self.module = module
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.group = group
        self.axis = group.axis_name
        self.donate = donate
        self.compute_dtype = compute_dtype
        self.accum_steps = accum_steps
        self.shard_optimizer = shard_optimizer
        # the group a leaf is divided over: 1 = every update whole
        self._shard_n = group.size() if shard_optimizer is not False else 1
        self.comm_dtype = comm_dtype
        if sync_batchnorm:
            convert_sync_batchnorm(module, self.axis)
        self._train_step = None
        self._train_chunk = None
        self._dispatched = 0    # steps handed to the device: spans' step=
        self._plan = None       # update_plan(), once parameter shapes are seen
        self._train_repeat_cache = {}
        self._eval_step = None
        self._forward = None

    # -- state ----------------------------------------------------------------
    def init(self, seed: int = 0, rng: Optional[jax.Array] = None) -> TrainState:
        """Build replicated TrainState.

        Deterministic given ``seed`` — every process constructs identical
        parameters, the TPU analogue of ``torch.manual_seed(0)`` before DDP
        wrap (/root/reference/mpspawn_dist.py:56).  (DDP's alternative —
        rank-0 broadcast at wrap time, /root/reference/example_mp.py:53 —
        is unnecessary when init is deterministic, but available as
        ``collectives.broadcast_host`` for externally-loaded params.)
        """
        with span("setup.init_state"):
            key = rng if rng is not None else jax.random.key(seed)
            params = self.module.init(key)
            model_state = self.module.init_state()
            opt_state = ({} if self.optimizer is None
                         else self.optimizer.init(params))
            state = TrainState(
                params, model_state, opt_state, jnp.zeros((), jnp.int32),
                jax.random.key_data(jax.random.fold_in(key, 0x5eed)))
            # commit onto the mesh so donation reuses buffers; the layout
            # policy (replicated, but params and opt_state per leaf by
            # shard_axis) lives in state_shardings so checkpoints restore to
            # exactly this placement
            return jax.tree.map(jax.device_put, state,
                                self.state_shardings(state))

    def state_shardings(self, state: TrainState) -> TrainState:
        """Pytree of :class:`NamedSharding` mirroring ``state``'s layout:
        everything replicated except the ``params`` and ``opt_state`` leaves
        a sharded update divides, each along its :func:`shard_axis`.  Feed to
        ``tpu_dist.checkpoint.restore(sharding=...)`` so a restored
        TrainState lands with its original placement."""
        mesh = self.group.mesh
        return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                            self._state_pspecs(state))

    def update_plan(self) -> dict:
        """What the weight update shards, host facts fixed at build:
        ``world`` (the group's size), ``sharded_leaves`` /
        ``sharded_elements`` (parameter leaves held and updated 1/world a
        replica along their :func:`shard_axis`, and their elements),
        ``whole_leaves`` / ``whole_elements`` (updated whole on every
        replica).  Also on the first ``td/train.dispatch`` span."""
        if self._plan is None:
            self._plan = self._plan_of(
                jax.eval_shape(self.module.init, jax.random.key(0)))
        return dict(self._plan)

    def _plan_of(self, params) -> dict:
        plan = {"world": self.group.size(), "sharded_leaves": 0,
                "whole_leaves": 0, "sharded_elements": 0, "whole_elements": 0}
        for leaf in jax.tree.leaves(params):
            kind = ("whole" if shard_axis(leaf.shape, self._shard_n) is None
                    else "sharded")
            plan[kind + "_leaves"] += 1
            plan[kind + "_elements"] += int(leaf.size)
        return plan

    # -- compiled steps --------------------------------------------------------
    def _state_pspecs(self, template: TrainState) -> TrainState:
        """PartitionSpec pytree for TrainState, a spec a leaf: replicated,
        except a sharded update's params and opt_state (:func:`_zero1_spec`).
        The one place that says so: :meth:`state_shardings` and the train
        step's in/out_specs both read it."""
        held = lambda tree: jax.tree.map(
            lambda l: _zero1_spec(l, self.axis, self._shard_n), tree)
        return jax.tree.map(lambda _: P(), template)._replace(
            params=held(template.params),
            opt_state=held(template.opt_state))

    def _make_local_step(self, template: TrainState):
        module, loss_fn, optimizer, axis = (self.module, self.loss_fn,
                                            self.optimizer, self.axis)
        has_state = module.has_state()
        accum = self.accum_steps
        cdtype = self.compute_dtype
        comm_dtype = self.comm_dtype
        n = self.group.size()
        # the axis each parameter leaf is divided along (None: stays whole),
        # in tree-flatten order
        dims = [shard_axis(leaf.shape, self._shard_n)
                for leaf in jax.tree.leaves(template.params)]
        self._plan = self._plan_of(template.params)

        def reduce_grad(g, d):
            """The mean gradient over the group: whole by all-reduce, or
            this replica's 1/n along ``d`` by reduce-scatter.  Comm-hook
            compression (torch DDP fp16/bf16_compress_hook semantics):
            divide by world size BEFORE the cast so the compressed-dtype
            sum cannot overflow (fp16 max 65504), move comm_dtype bytes on
            the wire, and decompress to the gradient's dtype after the
            reduce — accumulation and the optimizer update stay in the
            uncompressed dtype."""
            if d is None:
                total = partial(lax.psum, axis_name=axis)
            else:
                total = partial(lax.psum_scatter, axis_name=axis,
                                scatter_dimension=d, tiled=True)
            if comm_dtype is None or not jnp.issubdtype(g.dtype,
                                                        jnp.floating):
                return lax.pmean(g, axis) if d is None else total(g) / n
            return total((g / n).astype(comm_dtype)).astype(g.dtype)

        treedef = jax.tree.structure(template.params)
        on_leaves = lambda f, tree: jax.tree_util.tree_unflatten(
            treedef, [f(v, d) for v, d in
                      zip(treedef.flatten_up_to(tree), dims)])

        def local_step(state: TrainState, x, y):
            held, mstate, opt_state, step, rng_data = state
            base_key = jax.random.wrap_key_data(rng_data)
            # a divided leaf arrives as this replica's 1/n: make it whole
            # (and replicated) for the forward and backward passes
            with jax.named_scope("param_gather"):
                params = on_leaves(
                    lambda p, d: p if d is None else all_gather_invariant(
                        p, axis, axis=d, tiled=True), held)

            # Microbatch gradient: params are made device-varying (pvary) so
            # jax.grad yields LOCAL gradients with no implicit collective —
            # the all-reduce happens exactly once, after accumulation
            # (torch DDP `no_sync` accumulation semantics).
            p_var = jax.tree.map(lambda v: lax.pcast(v, axis, to="varying"), params)

            def micro(carry, xy):
                g_acc, loss_acc, correct_acc, ms, i = carry
                xb, yb = xy
                # per-step, per-microbatch, per-replica key (SURVEY.md §7:
                # dropout must differ across ranks)
                key = jax.random.fold_in(
                    jax.random.fold_in(base_key, step * accum + i),
                    lax.axis_index(axis))

                def loss_local(p):
                    if cdtype is not None:
                        with jax.named_scope("cast_params"):
                            p = jax.tree.map(
                                lambda v: v.astype(cdtype)
                                if jnp.issubdtype(v.dtype, jnp.floating)
                                else v, p)
                    xc = (xb.astype(cdtype)
                          if cdtype is not None and
                          jnp.issubdtype(xb.dtype, jnp.floating) else xb)
                    if has_state:
                        out, new_ms = module.apply(p, xc, state=ms,
                                                   training=True, rng=key)
                        # keep the f32 state master under bf16 compute:
                        # purely activation-derived leaves (MoE aux_loss)
                        # come back in compute_dtype, which would flip the
                        # scan carry's dtype (BatchNorm stats hide this —
                        # blending with the f32 running value re-promotes)
                        if cdtype is not None:
                            new_ms = jax.tree.map(
                                lambda n, o: n.astype(o.dtype), new_ms, ms)
                    else:
                        out = module.apply(p, xc, training=True, rng=key)
                        new_ms = ms
                    with jax.named_scope("loss"):
                        return loss_fn(out, yb), (out, new_ms)

                (loss, (out, new_ms)), g = jax.value_and_grad(
                    loss_local, has_aux=True)(p_var)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                correct = (out.argmax(-1) == yb).sum()
                return (g_acc, loss_acc + loss, correct_acc + correct,
                        new_ms, i + 1), None

            if accum > 1:
                xm = x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
                ym = y.reshape((accum, y.shape[0] // accum) + y.shape[1:])
                g0 = jax.tree.map(
                    lambda v: lax.pcast(jnp.zeros(v.shape, jnp.float32),
                                        axis, to="varying"), params)
                init = (g0,
                        lax.pcast(jnp.zeros((), jnp.float32), axis, to="varying"),
                        lax.pcast(jnp.zeros((), jnp.int32), axis, to="varying"),
                        mstate, 0)
                (g_sum, loss_sum, correct_sum, new_ms, _), _ = lax.scan(
                    micro, init, (xm, ym))
                local_grads = jax.tree.map(lambda g: g / accum, g_sum)
                loss = lax.pmean(loss_sum / accum, axis)
                correct = lax.psum(correct_sum, axis)
            else:
                # fast path: no accumulation scaffolding in the graph
                zero = jax.tree.map(jnp.zeros_like, p_var)
                (g_sum, loss_sum, correct_sum, new_ms, _), _ = micro(
                    (zero, 0.0, 0, mstate, 0), (x, y))
                local_grads = g_sum
                loss = lax.pmean(loss_sum, axis)
                correct = lax.psum(correct_sum, axis)

            # Cross-replica sharding of the weight update, leaf by leaf: a
            # replica receives 1/n of each divided leaf's mean gradient and
            # updates the 1/n of the parameter it holds with its 1/n of the
            # moments; none of the three leaves it, and the next step's
            # gather is what makes the parameter whole again.  A leaf that
            # stays whole takes the all-reduce and the whole update, as
            # every leaf does at n == 1.
            with jax.named_scope("grad_reduce"):
                grads = on_leaves(reduce_grad, local_grads)
            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizer.update(grads, opt_state, held)

            if has_state:
                # keep replicated-state invariant: average the per-replica
                # running-stat updates (see module docstring)
                new_ms = jax.tree.map(lambda v: lax.pmean(v, axis), new_ms)
            new_state = TrainState(new_params, new_ms, new_opt, step + 1,
                                   rng_data)
            return new_state, {"loss": loss, "correct": correct}

        return local_step

    def _build_train_step(self, template: TrainState):
        state_spec = self._state_pspecs(template)
        fn = jax.shard_map(self._make_local_step(template),
                           mesh=self.group.mesh,
                           in_specs=(state_spec, P(self.axis), P(self.axis)),
                           out_specs=(state_spec, P()))
        return jax.jit(fn, donate_argnums=(0,) if self.donate else ())

    def _build_train_chunk(self, template: TrainState):
        local_step = self._make_local_step(template)

        def local_chunk(state, xs, ys):
            def body(st, xy):
                return local_step(st, xy[0], xy[1])
            return lax.scan(body, state, (xs, ys))

        state_spec = self._state_pspecs(template)
        fn = jax.shard_map(local_chunk, mesh=self.group.mesh,
                           in_specs=(state_spec, P(None, self.axis),
                                     P(None, self.axis)),
                           out_specs=(state_spec, P()))
        return jax.jit(fn, donate_argnums=(0,) if self.donate else ())

    def _build_train_repeat(self, template: TrainState, num_steps: int):
        local_step = self._make_local_step(template)

        def local_repeat(state, x, y):
            def body(st, _):
                return local_step(st, x, y)
            return lax.scan(body, state, None, length=num_steps)

        state_spec = self._state_pspecs(template)
        fn = jax.shard_map(local_repeat, mesh=self.group.mesh,
                           in_specs=(state_spec, P(self.axis), P(self.axis)),
                           out_specs=(state_spec, P()))
        return jax.jit(fn, donate_argnums=(0,) if self.donate else ())

    def _build_eval_step(self):
        module, loss_fn, axis = self.module, self.loss_fn, self.axis
        has_state = module.has_state()
        ignore = getattr(loss_fn, "ignore_index", None)

        # takes only (params, model_state): feeding the whole TrainState
        # would re-lay-out a sharded opt_state to replicated (an
        # all-gather of optimizer moments) on every eval batch.  Held
        # parameters are gathered by the P() in_spec, once a call
        def local_eval(params, mstate, x, y, n_valid):
            out = module.apply(params, x,
                               **({"state": mstate} if has_state else {}))
            if has_state:
                out, _ = out
            # rows at global index >= n_valid are evaluate()'s batch
            # padding; under P(axis) sharding device d holds the
            # contiguous slice starting at d * rows_per_device
            rows = y.shape[0]
            gidx = lax.axis_index(axis) * rows + jnp.arange(rows)
            row_keep = (gidx < n_valid).reshape(
                (rows,) + (1,) * (y.ndim - 1))
            hit = out.argmax(-1) == y
            if ignore is not None:
                # scored = labels the loss actually counts (ignore_index
                # excluded) — exact even when padding lands unevenly
                # across devices: loss_sum = sum over scored labels, not
                # a mean of per-device means.  Padding rows carry
                # ignore_index labels, so row_keep only re-excludes them;
                # it additionally guards a pathological loss_fn whose
                # ignore_index the padding labels can't use.  (For
                # weight= losses the mean's denominator is the weight
                # sum, so loss_sum is approximate there.)
                local_mean = loss_fn(out, y)
                keep = (y != ignore) & row_keep
                kept = keep.sum()
                # mask the numerator too: if ignore_index is a valid class
                # id (torch permits >= 0), argmax CAN equal it at ignored
                # positions — unmasked, accuracy would exceed 1.0
                hit = hit & keep
                loss_sum = local_mean * kept
            else:
                # loss_fn has no ignore_index: it would score padding
                # rows.  Recover exact per-row losses by running the
                # black-box loss on batch-1 slices (a vmapped mean over
                # one row IS that row's loss) and sum only valid rows,
                # each weighted by its element count.
                per_row = jax.vmap(
                    lambda o, t: loss_fn(o[None], t[None]))(out, y)
                elems = y[0].size if y.ndim > 1 else 1
                keep_rows = row_keep.reshape(rows)
                loss_sum = (per_row * keep_rows).sum() * elems
                kept = keep_rows.sum() * elems
                hit = hit & jnp.broadcast_to(row_keep, hit.shape)
            loss_sum = lax.psum(loss_sum, axis)
            correct = lax.psum(hit.sum(), axis)
            scored = lax.psum(kept, axis)
            return {"loss": loss_sum / jnp.maximum(scored, 1),
                    "loss_sum": loss_sum, "correct": correct,
                    "scored": scored}

        fn = jax.shard_map(local_eval, mesh=self.group.mesh,
                           in_specs=(P(), P(), P(axis), P(axis), P()),
                           out_specs=P())
        return jax.jit(fn)

    # -- public API ------------------------------------------------------------
    def train_step(self, state: TrainState, x, y):
        """One fused fwd+bwd+allreduce+update step; returns
        ``(new_state, {"loss": scalar, "correct": count})``."""
        if self.optimizer is None or self.loss_fn is None:
            raise ValueError("train_step requires optimizer= and loss_fn=")
        if self._train_step is None:
            self._train_step = self._build_train_step(state)
        # the first dispatch carries what the update shards
        plan = self._plan if self._dispatched == 0 else {}
        with span("train.dispatch", step=self._dispatched, **plan):
            out = self._train_step(state, x, y)
        self._dispatched += 1
        return out

    def train_chunk(self, state: TrainState, xs, ys):
        """Run ``xs.shape[0]`` fused train steps in ONE dispatch.

        ``xs``/``ys`` carry a leading steps axis: ``xs[i]`` is step *i*'s
        global batch (sharded over the data axis like ``train_step``'s).
        The steps execute as a ``lax.scan`` on device — semantically
        identical to ``k`` sequential :meth:`train_step` calls (tested),
        but with a single host dispatch and readback.  This is the
        TPU-idiomatic inner loop: host dispatch latency stops mattering
        when k steps ride one XLA program.

        Returns ``(new_state, metrics)`` where each metrics leaf is stacked
        per-step, shape ``(k,)`` — log ``metrics["loss"][-1]`` or the mean.
        """
        if self.optimizer is None or self.loss_fn is None:
            raise ValueError("train_chunk requires optimizer= and loss_fn=")
        if self._train_chunk is None:
            self._train_chunk = self._build_train_chunk(state)
        steps = int(xs.shape[0])
        plan = self._plan if self._dispatched == 0 else {}
        with span("train.dispatch", step=self._dispatched, steps=steps,
                  **plan):
            out = self._train_chunk(state, xs, ys)
        self._dispatched += steps
        return out

    def train_repeat(self, state: TrainState, x, y, num_steps: int):
        """``num_steps`` fused steps on the SAME batch in one dispatch.

        Like :meth:`train_chunk` but the batch is scan-invariant, so no
        ``(k, batch, ...)`` input is materialized — the per-step rng still
        advances (the step counter seeds dropout keys).  Uses: throughput
        measurement and overfit-one-batch debugging.
        Returns ``(new_state, metrics)`` with per-step ``(k,)`` leaves.
        """
        if self.optimizer is None or self.loss_fn is None:
            raise ValueError("train_repeat requires optimizer= and loss_fn=")
        fn = self._train_repeat_cache.get(num_steps)
        if fn is None:
            fn = self._build_train_repeat(state, num_steps)
            self._train_repeat_cache[num_steps] = fn
        return fn(state, x, y)

    def eval_step(self, state: TrainState, x, y, n_valid=None):
        """``n_valid`` = number of real (non-padding) leading rows in the
        global batch; defaults to all rows."""
        if self.loss_fn is None:
            raise ValueError("eval_step requires loss_fn=")
        if self._eval_step is None:
            self._eval_step = self._build_eval_step()
        if n_valid is None:
            n_valid = int(x.shape[0])
        return self._eval_step(state.params, state.model_state, x, y,
                               jnp.asarray(n_valid, jnp.int32))

    def evaluate(self, state: TrainState, loader) -> dict:
        """Drive :meth:`eval_step` over a loader of ``(x, y)`` batches;
        returns global ``{"loss", "accuracy", "count"}`` (the torch
        eval-loop idiom; metrics are identical on every process since
        ``eval_step`` reduces over the whole mesh).

        Partial batches are padded with ``ignore_index`` labels up to the
        first batch's size rounded to a multiple of the mesh's device count
        (one compiled shape, always divisible over the data axis).
        ``count`` is the number of labels the loss actually *scored*:
        samples for classification, non-``ignore_index`` tokens for
        sequence models — batch-padding rows and data-inherent padding
        tokens are both excluded, from the loss, the accuracy denominator,
        and the count (a padded label can never count as correct: argmax is
        in [0, C)).  Works for any loss_fn: with an ``ignore_index``
        attribute padding rows carry that label and the loss skips them;
        without one, padding rows carry label 0 and ``eval_step`` masks
        them positionally via the true row count (exact per-row losses via
        a vmapped batch-1 loss call).  Loss aggregates as
        sum-over-scored-labels / total-scored — exact under any padding
        distribution.  Metrics accumulate on device; the single host
        readback happens at the end (per-step ``float()`` would serialize
        eval over the dispatch latency).
        """
        ignore = getattr(self.loss_fn, "ignore_index", None)
        # without ignore_index semantics, pad with a valid label (0): the
        # padded rows are masked out positionally, and an arbitrary custom
        # loss may index with the label (-100 would be out of range)
        pad_label = 0 if ignore is None else ignore
        n_dev = self.group.size()
        pad_to = None
        total_loss = total_correct = total_scored = None
        for x, y in loader:
            b = int(x.shape[0])
            target = _ceil_to(b, n_dev)
            pad_to = target if pad_to is None else max(pad_to, target)
            if b < pad_to:
                x = jnp.concatenate(
                    [x, jnp.zeros((pad_to - b,) + x.shape[1:], x.dtype)])
                y = jnp.concatenate(
                    [y, jnp.full((pad_to - b,) + y.shape[1:], pad_label,
                                 y.dtype)])
            m = self.eval_step(state, x, y, n_valid=b)
            if total_loss is None:
                total_loss = m["loss_sum"]
                total_correct = m["correct"]
                total_scored = m["scored"]
            else:
                total_loss = total_loss + m["loss_sum"]
                total_correct = total_correct + m["correct"]
                total_scored = total_scored + m["scored"]
        if total_loss is None:
            return {"loss": 0.0, "accuracy": 0.0, "count": 0}
        n = int(total_scored)
        if n == 0:
            return {"loss": 0.0, "accuracy": 0.0, "count": 0}
        return {"loss": float(total_loss) / n,
                "accuracy": int(total_correct) / n, "count": n}

    def forward(self, state: TrainState, x):
        """Inference forward on a (data-axis-sharded) batch; returns logits
        sharded the same way (torch ``ddp_model(images)`` parity)."""
        if self._forward is None:
            module, has_state = self.module, self.module.has_state()

            def local_fwd(params, mstate, xx):
                out = module.apply(params, xx,
                                   **({"state": mstate} if has_state else {}))
                return out[0] if has_state else out

            fn = jax.shard_map(local_fwd, mesh=self.group.mesh,
                               in_specs=(P(), P(), P(self.axis)),
                               out_specs=P(self.axis))
            self._forward = jax.jit(fn)
        return self._forward(state.params, state.model_state, x)

"""Training through the reference's recipe: ``init_process_group`` +
``DistributedDataParallel`` with one replica per chip, ``train_step`` called
once per step on host batches placed with the group's sharding.

Untraced: steps are dispatched without a per-step readback; the loop blocks
on every ``block_every``-th loss and at the end, and tokens count for
completed steps only.  Traced: every step is blocked on (so a step's span is
its time) and a few steady steps in the middle are profiled.
"""

from __future__ import annotations

import contextlib

import numpy as np

from .. import spec, traffic
from ..clock import now
from ..spans import traced_slice


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import tpu_dist.dist as dist
    from tpu_dist import nn
    from tpu_dist.parallel import DistributedDataParallel

    cfg, mix, tr = ctx.config, ctx.mix, ctx.config["train"]
    if mix["kind"] != "lm_batches":
        raise SystemExit(f"train_ddp cannot run a {mix['kind']!r} mix")
    kw = spec.model_kwargs(cfg)
    pg = dist.init_process_group()
    group = (pg if pg.size() == ctx.chips
             else dist.new_group(ranks=list(range(ctx.chips))))
    ddp = DistributedDataParallel(
        spec.resolve(cfg["model"]["factory"])(**kw),
        optimizer=spec.resolve(tr["optimizer"])(**tr["optimizer_kwargs"]),
        loss_fn=nn.CrossEntropyLoss(fused=True), group=group,
        compute_dtype=jnp.dtype(tr["compute_dtype"]))
    state = jax.block_until_ready(ddp.init(seed=ctx.seed))
    ctx.mark("parameters and optimizer state from the seed")

    batch = mix["global_batch"]
    per_chip = batch // ctx.chips
    if per_chip * ctx.chips != batch or per_chip > tr["per_chip_batch"]:
        raise SystemExit(
            f"train_ddp: a global batch of {batch} over {ctx.chips} chip(s) "
            f"is not a whole per-chip batch of at most "
            f"{tr['per_chip_batch']} (the size the memory was fitted to)")
    tokens_per_step = batch * mix["seq_len"]
    batches = traffic.lm_batches(mix, ctx.seed, kw["vocab_size"], batch)
    shard = NamedSharding(group.mesh, P(group.axis_name))
    spans = ctx.spans
    losses = []
    first_batch = None

    def step(state):
        nonlocal first_batch
        with spans.span("batch"):
            x, y = next(batches)
        if first_batch is None:
            first_batch = (x, y)
        with spans.span("device_put"):
            xd, yd = jax.device_put(x, shard), jax.device_put(y, shard)
        with spans.span("train_step"):
            state, metrics = ddp.train_step(state, xd, yd)
            if ctx.trace:
                jax.block_until_ready(metrics["loss"])
        losses.append(metrics["loss"])
        return state

    for i in range(mix["warmup_steps"]):
        state = step(state)
        if i == 0:
            jax.block_until_ready(losses[0])
            ctx.mark("first step: trace, compile or load, run")
    jax.block_until_ready(losses[-1])
    ctx.mark("warm-up done")

    t0 = now()
    done = 0
    with contextlib.ExitStack() as tracing:
        while True:
            if ctx.trace and done == mix["trace_from_step"]:
                tracing.enter_context(traced_slice(ctx.trace_dir))
            if ctx.trace and done == (mix["trace_from_step"]
                                      + mix["trace_steps"]):
                tracing.close()
            state = step(state)
            done += 1
            if ctx.trace or done % mix["block_every"] == 0:
                with spans.span("block"):
                    jax.block_until_ready(losses[-1])
                if now() - t0 >= ctx.seconds:
                    break
    t1 = now()
    losses = [float(v) for v in jax.device_get(losses)]
    steps_total = len(losses)

    rate = done * tokens_per_step / (t1 - t0) / ctx.chips
    at = lambda k: losses[k - 1] if k <= steps_total else float("nan")
    print(f"[chipbench] {done} steps of {batch} x {mix['seq_len']} tokens in "
          f"{t1 - t0:.3f} s on {ctx.chips} chip(s): {rate:.1f} tokens/s "
          f"per chip; loss at steps 1, 10, 20: {at(1):.6f} {at(10):.6f} "
          f"{at(20):.6f} (warm-up steps included in the count)", flush=True)

    def verify():
        """Outside the window: the first step's loss against the plain
        reference on the same batch and the same (regenerated) initial
        parameters; every loss finite; the 20th below the first."""
        nonlocal state
        del state                          # parameters, moments: make room
        ref = spec.load_module(spec.find(ctx.bench, "reference",
                                         cfg["reference"]))
        params0 = ddp.module.init(jax.random.key(ctx.seed))
        stacked = ref.stack_params(cfg, params0)
        del params0
        ref_loss = jax.jit(lambda s, x, y: ref.loss(cfg, s, x, y))
        x, y = first_batch
        rows = tr["reference_rows"]
        want = float(np.mean([float(ref_loss(stacked, x[i:i + rows],
                                             y[i:i + rows]))
                              for i in range(0, batch, rows)]))
        diff = abs(losses[0] - want)
        print(f"[chipbench] first-step loss {losses[0]:.6f}, reference "
              f"{want:.6f}, |difference| {diff:.2e} (tolerance "
              f"{tr['loss_tol']:.1e}: {tr['loss_tol_reason']})", flush=True)
        ok = (diff <= tr["loss_tol"] and bool(np.all(np.isfinite(losses)))
              and steps_total >= 20 and losses[19] < losses[0])
        if not ok:
            print(f"[chipbench] INCORRECT: losses {losses[:20]}", flush=True)
        return ok

    return {"window": (t0, t1), "attempted": done,
            "failed": int(np.sum(~np.isfinite(losses[-done:]))),
            "end_to_end": {"train_tokens_per_s_per_chip": rate},
            "counters": {"steps": done, "tokens_per_step": tokens_per_step,
                         "per_chip_batch": per_chip,
                         "seq_len": mix["seq_len"]},
            "model_kwargs": kw, "verify": verify,
            "close": dist.destroy_process_group}

"""Long-context TransformerLM training — the beyond-parity workload.

The reference trains image classifiers only (SURVEY.md §2a); tpu_dist adds
sequence models with long-context parallelism as first-class citizens.  One
script, three parallelism modes over the same model:

  --parallel dp   DistributedDataParallel over all cores (default): batch
                  sharded on the 'data' axis, grad-allreduce fused by XLA;
                  attention runs the Pallas flash kernel on TPU
                  (tpu_dist.ops.flash_attention, O(T) memory).
  --parallel sp   2-D (data × seq) mesh: the SEQUENCE is sharded across
                  cores; each attention layer runs ring attention
                  (KV blocks rotate over ICI, --sp-mode ulysses for the
                  all-to-all head-redistribution variant).  Trains contexts
                  n_seq times longer than one core can hold.
  --parallel tp   GSPMD Megatron-style tensor parallelism on a
                  (data × model) mesh: QKV/MLP column+row sharded via
                  TRANSFORMER_TP_RULES; XLA inserts the all-reduces.
  --parallel pp   GPipe pipeline parallelism on a (data × pipe) mesh:
                  trunk blocks stacked + sharded over 'pipe' (optimizer
                  state sharded with them), microbatches flow stage to
                  stage over ICI ppermute hops inside one lax.scan.
  --parallel ep   Mixture-of-Experts expert parallelism on a (data ×
                  expert) mesh: every block's MLP becomes a top-2-routed
                  MoELayer, expert FFN weights sharded over 'expert'
                  (MOE_EP_RULES), token all-to-alls inserted by XLA,
                  Switch load-balance aux loss in the objective.

--lr-schedule warmup_cosine compiles a warmup+cosine decay schedule into
the jitted step (tpu_dist.optim.lr_scheduler) — the lr changes every step
with no recompile.

Synthetic task: next token = a fixed random permutation of the current
token — exactly learnable, so falling loss (printed rank-0 style, the
reference's logging discipline) is the correctness oracle.

Run (single host, all cores):     python examples/train_lm.py
Virtual 8-core CPU smoke test:    python examples/train_lm.py --backend cpu \
                                    --parallel sp --steps 20
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from datetime import datetime


def make_batches(rng, perm, vocab, batch, seq_len, steps):
    """Synthetic permutation-LM stream: y[t] = perm[x[t]]."""
    import numpy as np

    for _ in range(steps):
        x = rng.integers(0, vocab, (batch, seq_len))
        yield x, perm[x]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--parallel", default="dp",
                   choices=["dp", "sp", "tp", "pp", "ep"])
    p.add_argument("--sp-mode", default="ring", choices=["ring", "ulysses"])
    p.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    p.add_argument("--steps", default=200, type=int)
    p.add_argument("--batch-size", default=8, type=int,
                   help="global batch (split over the 'data' axis)")
    p.add_argument("--seq-len", default=512, type=int,
                   help="global sequence length (split over 'seq' under sp)")
    p.add_argument("--dim", default=256, type=int)
    p.add_argument("--depth", default=4, type=int)
    p.add_argument("--heads", default=8, type=int)
    p.add_argument("--vocab", default=256, type=int)
    p.add_argument("--lr", default=0.5, type=float)
    p.add_argument("--lr-schedule", default="none",
                   choices=["none", "warmup_cosine"],
                   help="compiled-in schedule (peak = --lr, 10%% warmup)")
    p.add_argument("--microbatches", default=0, type=int,
                   help="pp only: microbatch count (0 = one per stage)")
    p.add_argument("--experts", default=4, type=int,
                   help="ep only: expert count (rounded up to a multiple "
                        "of the 'expert' axis size)")
    p.add_argument("--log-every", default=20, type=int)
    p.add_argument("--generate", default=0, type=int,
                   help="after dp training: sample N tokens with the KV "
                        "cache and report how many transitions follow the "
                        "learned permutation (greedy at the default "
                        "--gen-temperature 0; --gen-top-k/--gen-top-p "
                        "apply only when --gen-temperature > 0)")
    p.add_argument("--gen-temperature", default=0.0, type=float)
    p.add_argument("--gen-top-k", default=0, type=int)
    p.add_argument("--gen-top-p", default=1.0, type=float)
    p.add_argument("--gen-int8", action="store_true",
                   help="quantize matmul weights to int8 before generating "
                        "(nn.quantize_linear_weights, attention included) — "
                        "the serving recipe; the permutation check still "
                        "has to pass on the quantized model")
    args = p.parse_args()

    if args.backend == "cpu":
        # 8 virtual CPU devices so sp/tp modes exercise a real mesh
        flag = "--xla_force_host_platform_device_count=8"
        if flag not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        os.environ["JAX_PLATFORMS"] = "cpu"   # before the first jax import

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.models import TransformerLM

    rng = np.random.default_rng(0)
    perm = rng.permutation(args.vocab)
    start = datetime.now()

    def make_lr():
        if args.lr_schedule == "warmup_cosine":
            return optim.warmup_cosine(peak_lr=args.lr,
                                       warmup_steps=max(args.steps // 10, 1),
                                       total_steps=args.steps)
        return args.lr

    if args.parallel == "dp":
        dist.init_process_group(backend=args.backend)
        pg = dist.get_default_group()
        n = dist.get_world_size()
        from tpu_dist.parallel import DistributedDataParallel

        model = TransformerLM(args.vocab, dim=args.dim, depth=args.depth,
                              num_heads=args.heads, max_seq_len=args.seq_len)
        ddp = DistributedDataParallel(
            model, optimizer=optim.SGD(lr=make_lr()),
            loss_fn=nn.CrossEntropyLoss(), group=pg)
        state = ddp.init(seed=0)
        shard = NamedSharding(pg.mesh, P(pg.axis_name))
        batch = max(args.batch_size // n, 1) * n
        for i, (x, y) in enumerate(make_batches(rng, perm, args.vocab,
                                                batch, args.seq_len,
                                                args.steps)):
            state, metrics = ddp.train_step(
                state, jax.device_put(x, shard), jax.device_put(y, shard))
            if dist.get_rank() == 0 and (i + 1) % args.log_every == 0:
                print(f"Step [{i + 1}/{args.steps}] "
                      f"loss: {float(metrics['loss']):.4f}")

        if args.generate > 0 and dist.get_rank() == 0:
            # the trained map is y[t] = perm[x[t]], so greedy decoding
            # iterates the permutation: each new token should be
            # perm[previous] — a self-checking generation demo
            gen_params = state.params
            if args.gen_int8:
                model, gen_params = nn.quantize_linear_weights(
                    model, jax.device_get(state.params), attention=True)
                print("generating with int8 matmul weights")
            prompt = jnp.asarray(rng.integers(0, args.vocab, (1, 4)))
            out = model.generate(
                gen_params, prompt, args.generate,
                temperature=args.gen_temperature,
                rng=(jax.random.key(1) if args.gen_temperature > 0
                     else None),
                top_k=args.gen_top_k, top_p=args.gen_top_p)
            seq = np.asarray(out[0])
            gen = seq[prompt.shape[1] - 1:]
            ok = sum(int(gen[i + 1]) == int(perm[gen[i]])
                     for i in range(len(gen) - 1))
            print(f"generate: {seq.tolist()}")
            print(f"permutation-consistent transitions: "
                  f"{ok}/{len(gen) - 1}")

    elif args.parallel == "sp":
        n = len(jax.devices())
        dp = 2 if n % 2 == 0 and n > 1 else 1
        sp = n // dp
        dist.init_process_group(backend=args.backend,
                                axis_names=("data", "seq"),
                                mesh_shape=(dp, sp))
        pg = dist.get_default_group()
        seq_len = max(args.seq_len // sp, 16) * sp     # divisible shards
        batch = max(args.batch_size // dp, 1) * dp
        model = TransformerLM(args.vocab, dim=args.dim, depth=args.depth,
                              num_heads=args.heads, max_seq_len=seq_len,
                              sequence_axis="seq", mode=args.sp_mode)
        params = model.init(jax.random.key(0))
        opt = optim.SGD(lr=make_lr())
        opt_state = opt.init(params)
        ce = nn.CrossEntropyLoss()

        def local_step(params, opt_state, x, y):
            def loss_local(p):
                logits = model.apply(p, x)    # pos offset auto from 'seq'
                loss = ce(logits.reshape(-1, args.vocab), y.reshape(-1))
                return lax.pmean(lax.pmean(loss, "seq"), "data")

            loss, grads = jax.value_and_grad(loss_local)(params)
            new_p, new_o = opt.update(grads, opt_state, params)
            return new_p, new_o, loss

        pspec = jax.tree.map(lambda _: P(), params)
        ospec = jax.tree.map(lambda _: P(), opt_state)
        step = jax.jit(jax.shard_map(
            local_step, mesh=pg.mesh,
            in_specs=(pspec, ospec, P("data", "seq"), P("data", "seq")),
            out_specs=(pspec, ospec, P())))
        shard = NamedSharding(pg.mesh, P("data", "seq"))
        for i, (x, y) in enumerate(make_batches(rng, perm, args.vocab,
                                                batch, seq_len, args.steps)):
            params, opt_state, loss = step(
                params, opt_state,
                jax.device_put(x, shard), jax.device_put(y, shard))
            if dist.get_rank() == 0 and (i + 1) % args.log_every == 0:
                print(f"Step [{i + 1}/{args.steps}] "
                      f"loss: {float(loss):.4f}  "
                      f"(seq {seq_len} over {sp} cores, {args.sp_mode})")

    elif args.parallel == "pp":
        n = len(jax.devices())
        dp = 2 if n % 2 == 0 and n > 1 else 1
        pipe = n // dp
        dist.init_process_group(backend=args.backend,
                                axis_names=("data", "pipe"),
                                mesh_shape=(dp, pipe))
        pg = dist.get_default_group()
        from tpu_dist.parallel import PipelineParallel

        depth = max(args.depth // pipe, 1) * pipe      # divisible stages
        model = TransformerLM(args.vocab, dim=args.dim, depth=depth,
                              num_heads=args.heads, max_seq_len=args.seq_len)
        pp_wrap = PipelineParallel(
            model, optimizer=optim.SGD(lr=make_lr()),
            loss_fn=nn.CrossEntropyLoss(),
            num_microbatches=args.microbatches or None)
        state = pp_wrap.init(seed=0)
        m_count = pp_wrap.num_microbatches
        batch = max(args.batch_size // (dp * m_count), 1) * dp * m_count
        bsh = NamedSharding(pg.mesh, P("data"))
        for i, (x, y) in enumerate(make_batches(rng, perm, args.vocab,
                                                batch, args.seq_len,
                                                args.steps)):
            state, metrics = pp_wrap.train_step(
                state, jax.device_put(x, bsh), jax.device_put(y, bsh))
            if dist.get_rank() == 0 and (i + 1) % args.log_every == 0:
                print(f"Step [{i + 1}/{args.steps}] "
                      f"loss: {float(metrics['loss']):.4f}  "
                      f"({pipe} stages x {m_count} microbatches)")

    elif args.parallel == "ep":
        n = len(jax.devices())
        dp = 2 if n % 2 == 0 and n > 1 else 1
        ep = n // dp
        dist.init_process_group(backend=args.backend,
                                axis_names=("data", "expert"),
                                mesh_shape=(dp, ep))
        pg = dist.get_default_group()
        from tpu_dist.parallel import (MOE_EP_RULES, make_gspmd_train_step,
                                       shard_pytree)

        # round UP to a multiple of the expert-axis size: the stacked expert
        # weights' leading dim must split evenly over P('expert')
        experts = -(-max(args.experts, 2) // ep) * ep
        model = TransformerLM(args.vocab, dim=args.dim, depth=args.depth,
                              num_heads=args.heads, max_seq_len=args.seq_len,
                              num_experts=experts)
        ce = nn.CrossEntropyLoss()
        opt = optim.SGD(lr=make_lr())
        params = shard_pytree(model.init(jax.random.key(0)), pg.mesh,
                              MOE_EP_RULES)
        mstate = shard_pytree(model.init_state(), pg.mesh)
        opt_state = opt.init(params)
        step = make_gspmd_train_step(
            model, lambda lg, y: ce(lg.reshape(-1, args.vocab),
                                    y.reshape(-1)), opt,
            aux_loss_coeff=0.01)
        batch = max(args.batch_size // dp, 1) * dp
        bsh = NamedSharding(pg.mesh, P("data", None))
        for i, (x, y) in enumerate(make_batches(rng, perm, args.vocab,
                                                batch, args.seq_len,
                                                args.steps)):
            params, opt_state, mstate, m = step(params, opt_state, mstate,
                                                jax.device_put(x, bsh),
                                                jax.device_put(y, bsh))
            if dist.get_rank() == 0 and (i + 1) % args.log_every == 0:
                aux = sum(float(v["aux_loss"]) for v in mstate.values()
                          if "aux_loss" in v)
                print(f"Step [{i + 1}/{args.steps}] "
                      f"loss: {float(m['loss']):.4f}  "
                      f"(E={experts} over {ep} cores, aux {aux:.3f})")

    else:  # tp
        n = len(jax.devices())
        dp = 2 if n % 2 == 0 and n > 1 else 1
        tp = n // dp
        dist.init_process_group(backend=args.backend,
                                axis_names=("data", "model"),
                                mesh_shape=(dp, tp))
        pg = dist.get_default_group()
        from tpu_dist.parallel import (TRANSFORMER_TP_RULES,
                                       make_gspmd_train_step, shard_pytree)

        heads = max(args.heads // tp, 1) * tp          # divisible heads
        model = TransformerLM(args.vocab, dim=args.dim, depth=args.depth,
                              num_heads=heads, max_seq_len=args.seq_len)
        ce = nn.CrossEntropyLoss()
        opt = optim.SGD(lr=make_lr())
        params = shard_pytree(model.init(jax.random.key(0)), pg.mesh,
                              TRANSFORMER_TP_RULES)
        opt_state = opt.init(params)
        step = make_gspmd_train_step(
            model, lambda lg, y: ce(lg.reshape(-1, args.vocab),
                                    y.reshape(-1)), opt)
        batch = max(args.batch_size // dp, 1) * dp
        bsh = NamedSharding(pg.mesh, P("data", None))
        for i, (x, y) in enumerate(make_batches(rng, perm, args.vocab,
                                                batch, args.seq_len,
                                                args.steps)):
            params, opt_state, m = step(params, opt_state,
                                        jax.device_put(x, bsh),
                                        jax.device_put(y, bsh))
            if dist.get_rank() == 0 and (i + 1) % args.log_every == 0:
                print(f"Step [{i + 1}/{args.steps}] "
                      f"loss: {float(m['loss']):.4f}  (tp={tp})")

    if dist.get_rank() == 0:
        print(f"Training complete in: {datetime.now() - start} "
              f"(platform {dist.get_backend()}, {dist.get_world_size()} x "
              f"{jax.devices()[0].device_kind})")
    dist.destroy_process_group()


if __name__ == "__main__":
    main()

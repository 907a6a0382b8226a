"""MoE TransformerLM training throughput — tokens/sec/chip on the real chip.

The EP ladder rung next to benchmarks/transformer_lm.py's dense 143k
tokens/sec row (VERDICT r2 #8): the same GPT-2-small trunk with every
block's MLP replaced by a top-2-routed 8-expert MoELayer (nn/moe.py GShard
dispatch/combine einsums, Switch aux loss carried in model state).

On the single real chip the expert axis is size 1 (experts replicated,
dp-only mesh) — the *sharded* dp×ep path with a multi-step optimizer loop
is proven separately on the 8-device dryrun (__graft_entry__._dryrun_dp_ep,
3 steps, MULTICHIP artifact) and in tests/test_moe.py; this row records
what a chip actually sustains running the MoE compute graph (router +
dispatch + 2-of-8 expert FFNs + combine) through the standard DDP bf16
fused step, timed with the same scan-differenced methodology as the dense
row.  ``dispatch="gather"`` (nn/moe.py index-map dispatch) is the default
here: the einsum path's GShard ``(N, E, C)`` dispatch/combine temps scale
with tokens x experts and OOM 16G HBM at the dense row's per-chip batch 8
(measured 29.8G), capping that path at batch 2 — gather dispatch carries
batch 8 and its better MXU utilization.
"""

from __future__ import annotations

import json


def run(batch: int = 8, seq_len: int = 2048, dim: int = 768,
        depth: int = 12, heads: int = 12, vocab: int = 32768,
        experts: int = 8, steps: int = 20, reps: int = 3,
        dispatch: str = "gather") -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.models import TransformerLM
    from tpu_dist.parallel import DistributedDataParallel

    from .timing import ddp_repeat_step_time

    own_group = not dist.is_initialized()
    pg = dist.init_process_group() if own_group else dist.get_default_group()
    n_chips = dist.get_world_size()

    model = TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                          num_heads=heads, max_seq_len=seq_len,
                          num_experts=experts, moe_dispatch=dispatch)
    ddp = DistributedDataParallel(
        model, optimizer=optim.SGD(lr=0.01),
        loss_fn=nn.CrossEntropyLoss(fused=True), group=pg, donate=True,
        compute_dtype=jnp.bfloat16)

    rng = np.random.default_rng(0)
    shard = NamedSharding(pg.mesh, P(pg.axis_name))
    x = jax.device_put(
        rng.integers(0, vocab, (batch * n_chips, seq_len)), shard)
    y = jax.device_put(
        rng.integers(0, vocab, (batch * n_chips, seq_len)), shard)

    sec = ddp_repeat_step_time(ddp, x, y, steps=steps, reps=reps)
    tok_s = batch * seq_len / sec

    shapes = jax.eval_shape(lambda: ddp.init(seed=0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(shapes.params))
    # active params per token: top-2 of `experts` expert FFNs + the rest
    expert_ffn = 2 * dim * 4 * dim * 2            # two matmuls, in+out
    n_active = n_params - depth * (experts - 2) * (expert_ffn // 2)
    flops_per_token = 3 * (2 * n_active + 4 * depth * seq_len * dim)
    tflops = tok_s * flops_per_token / 1e12

    if own_group:
        dist.destroy_process_group()
    return {
        "metric": "transformer_moe_lm_bf16_train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/sec/chip",
        "step_ms": round(sec * 1e3, 2),
        "model": {"params_M": round(n_params / 1e6, 1),
                  "active_params_M": round(n_active / 1e6, 1),
                  "dispatch": dispatch,
                  "experts": experts, "top_k": 2, "depth": depth,
                  "dim": dim, "heads": heads, "seq_len": seq_len,
                  "per_chip_batch": batch, "vocab": vocab},
        "achieved_model_tflops_active": round(tflops, 2),
        "n_chips": n_chips,
        "ep_sharded_multistep_proof": "__graft_entry__._dryrun_dp_ep "
                                      "(3 optimizer steps on dp x ep mesh)",
    }


if __name__ == "__main__":
    print(json.dumps(run()))

"""GSPMD parallelism: shard by annotation, let XLA insert the collectives.

The second parallel programming model next to the explicit ``shard_map`` DDP
wrapper (ddp.py).  Here you write ordinary single-device training code; the
*placement of the inputs* (params sharded per rules, batch sharded over
'data') drives XLA's SPMD partitioner to cut every matmul and insert every
collective — the scaling-book recipe: pick a mesh, annotate shardings,
profile, iterate.

This is how tensor parallelism is done TPU-first: no Megatron-style
Column/RowParallelLinear classes — a *rule* maps parameter paths to
PartitionSpecs (e.g. attention QKV sharded on the 'model' axis column-wise,
the output projection row-wise) and XLA emits exactly the all-reduces those
hand-written layers would contain.  Works combined with data parallelism on
an N-D mesh (('data', 'model') tested in tests/test_gspmd.py against the
single-device step).

The reference has no TP (SURVEY.md §2c) — this exists so the mesh design
demonstrably extends beyond DDP, as §2c's implication row requires.
"""

from __future__ import annotations

import re
from typing import Callable, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import rules as _rules

__all__ = ["PartitionRules", "shard_pytree", "make_gspmd_train_step",
           "TRANSFORMER_TP_RULES", "MOE_EP_RULES"]


class PartitionRules:
    """Ordered (path-regex → PartitionSpec) rules; first match wins.

    Paths are the flattened pytree key strings, e.g.
    ``"['block0.attn']['qkv_weight']"``; regexes are searched, not
    fullmatched.  Unmatched leaves replicate (P()).
    """

    def __init__(self, rules: Sequence[Tuple[str, P]]):
        self.rules = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(self, path: str, leaf=None) -> P:
        for pat, spec in self.rules:
            if pat.search(path):
                return spec
        return P()

    def tree_specs(self, tree):
        """Pytree of PartitionSpecs matching ``tree``'s structure."""
        flat = jax.tree_util.tree_leaves_with_path(tree)
        leaves = [self.spec_for(jax.tree_util.keystr(p), l) for p, l in flat]
        treedef = jax.tree_util.tree_structure(tree)
        return jax.tree_util.tree_unflatten(treedef, leaves)


# Megatron-style transformer sharding over a 'model' mesh axis:
# - fused QKV and MLP-in sharded column-wise (output features),
# - attention-out and MLP-out sharded row-wise (input features) — XLA
#   places the single all-reduce after each row-parallel matmul,
# - embeddings and LM head sharded on the vocab/feature dimension.
# Derived from the unified rule plane (parallel/rules.py): the same
# DEFAULT_RULES + layout table that drives ZeRO shards, reshard
# manifests and serving spans produces these specs (golden-pinned to
# the pre-refactor literals in tests/test_rules).
TRANSFORMER_TP_RULES = PartitionRules(_rules.partition_pairs())

# Expert parallelism over an 'expert' mesh axis: every stacked MoE leaf
# (w1/b1/w2/b2, a gated expert's w3; leading dim = num_experts; see
# nn/moe.py) shards its expert axis; the router and everything else
# replicate.  The dispatch/combine
# einsums then partition over 'expert' and XLA inserts the token
# all-to-alls the GShard paper wires by hand.
MOE_EP_RULES = PartitionRules(_rules.partition_pairs({"expert": "expert"}))


def shard_pytree(tree, mesh, rules: Optional[PartitionRules] = None):
    """``device_put`` every leaf onto ``mesh`` per ``rules`` (default:
    replicate everything).  The committed shardings then steer jit."""
    specs = (rules.tree_specs(tree) if rules is not None
             else jax.tree.map(lambda _: P(), tree))
    return jax.tree.map(
        lambda leaf, spec: (None if leaf is None else
                            jax.device_put(leaf, NamedSharding(mesh, spec))),
        tree, specs,
        is_leaf=lambda x: x is None)


def make_gspmd_train_step(model, loss_fn, optimizer, donate: bool = True,
                          aux_loss_coeff: float = 0.0) -> Callable:
    """Build the jitted GSPMD step: ordinary single-device code, sharded by
    its inputs.  Callers place params/opt_state with :func:`shard_pytree`
    and the batch with a ``P('data', ...)`` sharding; returns
    ``step(params, opt_state, x, y) -> (params, opt_state, metrics)`` —
    or, when the model carries mutable state (BatchNorm stats, MoE aux
    losses), ``step(params, opt_state, mstate, x, y) -> (params, opt_state,
    new_mstate, metrics)``.

    ``aux_loss_coeff``: weight on the sum of every ``aux_loss`` entry the
    state carries (MoE load balancing, nn/moe.py) — the entries are traced
    values of the same forward, so gradients flow through the routers.

    NOTE vs the shard_map DDP wrapper: under GSPMD, batch statistics (e.g.
    BatchNorm) are computed over the **global** batch — sync-BN semantics —
    because the program is written globally.  The shard_map wrapper is the
    one matching torch DDP's per-replica BN exactly.
    """
    has_state = model.has_state()

    def run_model(p, ms, x):
        # dense attention under GSPMD: XLA's SPMD partitioner cannot cut
        # a Pallas custom call, so the flash kernel must not be
        # auto-dispatched inside a sharded jit (see nn.attention)
        from ..nn.attention import attention_impl
        with attention_impl("dense"):
            if has_state:
                return model.apply(p, x, state=ms, training=True)
            return model.apply(p, x), ms

    def objective(p, ms, x, y):
        out, new_ms = run_model(p, ms, x)
        loss = loss_fn(out, y)
        aux = sum((v["aux_loss"] for v in new_ms.values()
                   if isinstance(v, dict) and "aux_loss" in v),
                  start=0.0) if has_state else 0.0
        return loss + aux_loss_coeff * aux, (loss, out, new_ms)

    def stateless_step(params, opt_state, x, y):
        (_, (loss, out, _)), grads = jax.value_and_grad(
            objective, has_aux=True)(params, {}, x, y)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        correct = (out.argmax(-1) == y).sum()
        return new_params, new_opt, {"loss": loss, "correct": correct}

    def stateful_step(params, opt_state, mstate, x, y):
        (_, (loss, out, new_ms)), grads = jax.value_and_grad(
            objective, has_aux=True)(params, mstate, x, y)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        correct = (out.argmax(-1) == y).sum()
        return new_params, new_opt, new_ms, {"loss": loss,
                                             "correct": correct}

    fn = stateful_step if has_state else stateless_step
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())

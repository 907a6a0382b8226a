"""Share of the model's parameter elements whose weight update is divided
over the cell's chips (each chip updates 1/chips of the leaf from its part of
a reduce-scattered gradient) rather than made whole on every chip: 0 on one
chip, ~99.9 where every matrix has an axis the group size divides.  Host
arithmetic by the program's own ``tpu_dist.parallel.ddp.shard_axis`` (the
function ``DistributedDataParallel`` partitions by, which ``update_plan()``
counts with) over the shapes ``jax.eval_shape`` gives the configuration's
model; no device read.  A program without the function, as the parent of
PR 37 is, reports nothing."""

import importlib

from chipbench import spec


def read(run):
    axis = getattr(importlib.import_module("tpu_dist.parallel.ddp"),
                   "shard_axis", None)
    if axis is None:
        return None
    import jax
    model = spec.resolve(run.ctx.config["model"]["factory"])(
        **run.model_kwargs)
    leaves = jax.tree.leaves(jax.eval_shape(model.init, jax.random.key(0)))
    sharded = sum(l.size for l in leaves
                  if axis(l.shape, run.ctx.chips) is not None)
    total = sum(l.size for l in leaves)
    return 100.0 * sharded / total if total else None

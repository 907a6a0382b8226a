"""Sub-tiles of scores a causal flash-attention call executes over the
visible (query, key) pairs the mathematics needs, in sub-tile areas: what the
kernels' skipping leaves of the masked half of T^2 (1.0 = nothing masked or
padded is computed; 2.0 = the whole square).  Host arithmetic on the cell's
shapes by the program's own ``tpu_dist.ops.flash_attention.tile_plan``, the
arithmetic the kernels' loops run on; no device read.  A program without
the function, as the parent of PR 33 is, reports nothing."""

import importlib


def read(run):
    # ``tpu_dist.ops.flash_attention`` the attribute is the function
    plan = getattr(importlib.import_module("tpu_dist.ops.flash_attention"),
                   "tile_plan", None)
    if plan is None:
        return None
    t = run.counters["seq_len"]
    made = plan(t, t, True, dtype=run.ctx.config["train"]["compute_dtype"])
    return made["executed"] / made["needed"] if made["needed"] else None

"""The share of the window's model time that the gated short convolution
layers would take at the chip's peaks: what ``SlotEngine.stats()["conv"]``
says the pool programs' calls carried through those layers (the requests'
rows and the calls, by program), priced by chipbench.conv_need (a layer's
three matrices read once a call, a row's activations moved once, a slot's
tail read and written once, 2 x rows x parameters operations; widths from
``hidden_size`` and ``conv_L_cache``), over the time the serving loop charged
the two pool programs (the sums of ``hist_prefill`` and ``hist_token``,
collection to collection).  What a kept trace's
``blockN/attn/{in_proj,conv,out_proj}`` scopes take over this share is the
mixer's distance from its roofline (PERF.md section 5).  A program without
the counter, as the parent of PR 47 is, a model without such a layer and a
run with no chip's peaks report nothing."""

import jax.numpy as jnp

from chipbench import conv_need
from chipbench.readers import engine_hist


def read(run):
    conv = run.counters.get("engine", {}).get("conv")
    cfg = run.ctx.config
    if not conv or run.peak is None or "conv_L_cache" not in cfg:
        return None
    charged = [h["mean"] * h["count"]
               for h in (engine_hist(run, "prefill"),
                         engine_hist(run, "decode_step")) if h]
    return conv_need.need_share(
        conv, cfg["hidden_size"], cfg["conv_L_cache"], sum(charged),
        run.peak, jnp.dtype(cfg["serve"]["param_dtype"]).itemsize)

"""Xing4.0 — the DeepSeek-V3 block inside a residual of ``hc_mult`` streams
mixed by manifold-constrained hyper-connections
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B, ``config.json``,
``model_type: xing4_0``; Xie et al., arXiv:2512.24880;
chipbench/reference/xing4.py is the plain form of the same equations).

    open:   X[i] = embedding, i < hc_mult
    a block, for each of its two sublayers F (attention behind ln1, the FFN
    behind ln2), with that sublayer's own hyper-connection:
        u, (Hpost, Hres) = hc(X);   X <- Hres X + Hpost F(N(u))
    close:  x = sum_i X[i];  final N;  untied head

Attention (latent, YaRN), the leading dense layers, the expert layers and
their router are :class:`KimiK2LM`'s, built by ITS constructor from the same
published scalars; what this model adds is the one argument that says how a
sublayer's output joins the residual (:class:`nn.HyperConnection`, from the
five published ``hc_*`` / ``mhc_*`` keys).  The blocks are
:class:`TransformerBlock`s and everything else (embedding, forward, the slot
cache, the pool programs' two methods, ``generate``) is
:class:`TransformerLM`'s: the streams live inside one forward pass and are
never cached.  ``num_nextn_predict_layers`` is 1 in the published
configuration and the multi-token-prediction layer is NOT built (the
published DeepSeek-V3 serving code drops it on load; a step that yields
other than one token a row is ROADMAP Reach 10).
"""

from __future__ import annotations

from .. import nn
from ..nn.moe import experts_around_a_common_one
from .kimi_k2 import KimiK2LM

__all__ = ["Xing4LM"]

#: Seeded weights (``Xing4LM.init``; the published configuration has no key
#: for either, chipbench/configs/xing4-29b-a4b-serve.json ``assumed`` has the
#: reasons and the counts).  The embedding's deviation: at ``nn.Embedding``'s
#: 1 the residual is nine tenths embedding after six layers, the streams stay
#: copies of one another and no mix of them reaches the logits.
EMBEDDING_STD = 0.3
#: A layer's routed experts are ONE drawn expert plus this share of a draw of
#: their own: bfloat16 and float32 decide a near-tie in a router's top k
#: differently at about one token in a hundred a layer, and with every expert
#: held a swap of two independent experts moves that token's logits as far as
#: a float8 computation moves every token's.
EXPERT_DEVIATION = 0.0625


class Xing4LM(KimiK2LM):
    """``hc_mult`` streams, ``hc_sinkhorn_iters`` normalisations of the
    stream-to-stream matrix with ``hc_eps`` in their denominators, its logits
    clamped to ``[mhc_h_res_clamp_min, mhc_h_res_clamp_max]``; every other
    argument is :class:`KimiK2LM`'s
    (chipbench/configs/xing4-29b-a4b-serve.json maps the published keys)."""

    def __init__(self, *, dim: int, hc_mult: int = 4,
                 hc_sinkhorn_iters: int = 20, hc_eps: float = 1e-6,
                 mhc_h_res_clamp_min: float = -30.0,
                 mhc_h_res_clamp_max: float = 30.0, norm_eps: float = 1e-6,
                 **kimi_k2):
        super().__init__(
            dim=dim, norm_eps=norm_eps, **kimi_k2,
            residual=lambda: nn.HyperConnection(
                dim, hc_mult, sinkhorn_iters=hc_sinkhorn_iters, eps=hc_eps,
                res_clamp_min=mhc_h_res_clamp_min,
                res_clamp_max=mhc_h_res_clamp_max, norm_eps=norm_eps))

    def init(self, key):
        """:class:`KimiK2LM`'s parameters with the embedding at
        ``EMBEDDING_STD`` and every expert layer's routed experts drawn
        around a common one (``EXPERT_DEVIATION``)."""
        params = super().init(key)
        params["tok"]["weight"] = EMBEDDING_STD * params["tok"]["weight"]
        for i, kind in enumerate(self.layer_kinds):
            if kind == "moe":
                params[f"block{i}.mlp"] = experts_around_a_common_one(
                    params[f"block{i}.mlp"], key, 1000 * (i + 1),
                    EXPERT_DEVIATION)
        return params

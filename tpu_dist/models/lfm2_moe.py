"""LFM2-MoE — a hybrid decoder whose token mixer is, layer by layer from a
published LIST, a gated short convolution or a grouped-query attention, and
whose feed-forward is a dense SwiGLU in the leading layers and then 64
sigmoid-routed experts with a selection bias and no shared expert
(https://huggingface.co/LiquidAI/LFM2-24B-A2B, ``config.json``,
``model_type: lfm2_moe``; the published ``modeling_lfm2_moe.py``;
chipbench/reference/lfm2_moe.py is the plain form of the same equations).

    h = x + Op_i(N(x));  y = h + FFN_i(N(h));  final N;  untied head
    N(x) = x * rsqrt(mean(x^2) + eps) * w
    Op_i  = nn.GatedShortConv where ``layer_types[i]`` is ``"conv"``,
            nn.MultiheadSelfAttention (grouped queries, a norm over every
            query and key head before the rotation, rope over the whole
            head, no bias) where it is ``"full_attention"``
    FFN_i = dense SwiGLU for i < num_dense_layers, else the expert layer:
            sigmoid scores, the ``top_k`` of largest score + bias, weights
            the unbiased scores over their sum, times
            ``routed_scaling_factor``

Two parametrisations are the repo's and not the published file's, the same
function either way (the configuration lists both under ``departures``): a
head norm's weight is held zero-centred (``x_hat * (1 + w)``, ``w`` from
zero, where the published one is ``x_hat * w`` from one), and the picks'
weights are divided by ``max(sum, 1e-9)`` where the published form adds 1e-6
to the sum (3e-7 relative at four sigmoid scores).

Which layer is of which kind arrives as the published list itself
(:attr:`Lfm2MoeLM.mixer_kinds`), which is not periodic from layer 0 (``conv,
conv, full_attention, conv, conv, conv, full_attention, ...``); a model of
fewer layers than the list names (one pipeline stage's) builds the layers it
has and leaves the further entries to the further stages.  The blocks are
:class:`TransformerBlock`s whose token mixer and MLP are built here, and
everything else (embedding, forward, the slot cache of K/V columns beside
convolution tails, the pool programs' two methods, ``generate``) is
:class:`TransformerLM`'s.
"""

from __future__ import annotations

from .. import nn
from ..nn.moe import experts_around_a_common_one
from .transformer import TransformerBlock, TransformerLM, _make_norm

__all__ = ["Lfm2MoeLM"]

_MIXER_KINDS = ("conv", "full_attention")
#: Seeded weights (``Lfm2MoeLM.init``; the published configuration has no key
#: for either, chipbench/configs/lfm2-24b-a2b-serve.json ``assumed`` has the
#: reasons and the readings).  The embedding's deviation: a sublayer here
#: adds 0.06-0.12 to the residual whatever its size, so at ``nn.Embedding``'s
#: 1 the residual before the head is five sixths embedding, the logits are
#: the embedding's and the head's, and what ten layers compute hardly reaches
#: the comparison that decides ``correct``; at 0.3 the layers carry two
#: thirds of it (models/xing4.py's precedent; 0.1 was tried and told the
#: precisions apart no better: the configuration has the three readings).
EMBEDDING_STD = 0.3
#: A layer's routed experts are ONE expert drawn as
#: the dense layers' SwiGLU is, U(+-1/sqrt(fan_in)), plus this share of a draw
#: of their own at that scale: all 64 experts are held and four weigh a
#: quarter each, bfloat16 and float32 decide a near-tie at the router's
#: fourth place differently for a few tokens in a hundred a layer, and a swap
#: of two independent experts would move such a token's logits as far as the
#: arithmetic's precision moves every token's (models/xing4.py's precedent).
EXPERT_DEVIATION = 0.0625


def _kinds(layer_types) -> list:
    """The published list of layer kinds, or its comma-separated text
    (``"conv,conv,full_attention"``: how a configuration file whose harness
    hands a factory scalars alone carries a list)."""
    if isinstance(layer_types, str):
        layer_types = layer_types.split(",")
    kinds = [str(kind).strip() for kind in layer_types]
    unknown = sorted(set(kinds) - set(_MIXER_KINDS))
    if unknown:
        raise ValueError(f"layer_types names {unknown}; the kinds built are "
                         f"{list(_MIXER_KINDS)}")
    return kinds


class Lfm2MoeLM(TransformerLM):
    """Args are the published configuration's, under this repo's names
    (chipbench/configs/lfm2-24b-a2b-serve.json maps them).  ``layer_types``
    is the published list, as a sequence or as comma-separated text
    (:func:`_kinds`), at least ``depth`` entries long; ``num_dense_layers``
    the count of leading layers with a dense MLP; ``num_experts`` the
    router's width, every expert held, and ``moe_top_k`` its picks a
    token."""

    def __init__(self, vocab_size: int, dim: int, depth: int,
                 num_heads: int, num_kv_heads: int, layer_types,
                 dense_hidden: int, num_dense_layers: int = 2,
                 conv_kernel: int = 3, conv_bias: bool = False,
                 num_experts: int = 64, moe_top_k: int = 4,
                 moe_hidden: int = 1536, moe_normalize_gates: bool = True,
                 routed_scaling_factor: float = 1.0,
                 use_expert_bias: bool = True, rope_theta: float = 1e6,
                 norm_eps: float = 1e-5, max_seq_len: int = 128000):
        nn.Module.__init__(self)
        if conv_bias:
            raise NotImplementedError(
                "a convolution with a bias is not built: the published "
                "LFM2 sets conv_bias false")
        kinds = _kinds(layer_types)
        if len(kinds) < depth:
            raise ValueError(f"layer_types names {len(kinds)} layers, the "
                             f"model has {depth}")
        self.num_experts = num_experts
        #: ``"conv"`` or ``"full_attention"`` per layer (the published list)
        self.mixer_kinds = kinds[:depth]
        #: ``"dense"`` or ``"moe"`` per layer (the published count)
        self.layer_kinds = ["dense" if i < num_dense_layers else "moe"
                            for i in range(depth)]

        def mixer(kind):
            if kind == "conv":
                return nn.GatedShortConv(dim, conv_kernel=conv_kernel)
            return nn.MultiheadSelfAttention(
                dim, num_heads, bias=False, causal=True, rope=True,
                rope_theta=float(rope_theta), qk_norm="head",
                qk_norm_eps=norm_eps, num_kv_heads=num_kv_heads)

        def mlp(kind):
            if kind == "dense":
                return nn.GatedMLP(dim, dense_hidden)
            return nn.MoELayer(
                dim, num_experts, hidden=moe_hidden, top_k=moe_top_k,
                normalize_gates=moe_normalize_gates, dispatch="dropless",
                gated=True, shared_hidden=0, scoring="sigmoid",
                selection_bias=use_expert_bias,
                routed_scale=routed_scaling_factor)

        blocks = [TransformerBlock(
            dim, num_heads, norm="rmsnorm", norm_eps=norm_eps,
            mixer=mixer(mix), mlp=mlp(kind))
            for mix, kind in zip(self.mixer_kinds, self.layer_kinds)]
        self._assemble(vocab_size, dim, max_seq_len, blocks,
                       ln_f=_make_norm("rmsnorm", dim, norm_eps),
                       head=nn.Linear(dim, vocab_size, bias=False),
                       learned_pos=False)

    def init(self, key):
        """The parameters from ``key`` with the embedding at
        ``EMBEDDING_STD`` and every expert layer's routed experts drawn
        around a common one (``EXPERT_DEVIATION``).  The model is unchanged:
        a loaded checkpoint brings its own matrices."""
        params = super().init(key)
        params["tok"]["weight"] = EMBEDDING_STD * params["tok"]["weight"]
        for i, kind in enumerate(self.layer_kinds):
            if kind == "moe":
                # MoELayer draws a layer without a shared expert at the
                # kaiming bound, sqrt(6) times the dense layers'
                params[f"block{i}.mlp"] = experts_around_a_common_one(
                    params[f"block{i}.mlp"], key, 1000 * (i + 1),
                    EXPERT_DEVIATION / 6.0 ** 0.5)
        return params

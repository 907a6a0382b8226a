"""Kimi Linear — a hybrid decoder: Kimi Delta Attention (KDA) layers with a
multi-head LATENT attention layer WITHOUT positional encoding every fourth,
one leading dense layer and then routed expert layers with a shared expert,
scored by a sigmoid with a selection bias
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct,
``config.json``, ``model_type: kimi_linear``; the Kimi Linear report,
arXiv:2510.26692, and the published ``modeling_kimi.py``;
chipbench/reference/kimi_linear.py is the plain form of the same equations).

    h = x + Mixer_i(N(x));  y = h + FFN_i(N(h));  final N;  untied head
    N(x) = x * rsqrt(mean(x^2) + eps) * w
    Mixer_i = nn.KimiDeltaAttention where i + 1 is in ``kda_layers``,
              nn.MultiheadLatentAttention (no query rank, no rotation:
              ``q_lora_rank: null``, ``mla_use_nope: true``) where it is in
              ``full_attn_layers``
    FFN_i = dense SwiGLU for i < first_k_dense_replace (and off the
            moe_layer_freq grid), else the expert layer

Which layer is of which kind arrives as the two published LISTS of
``linear_attn_config`` (1-based), and the model derives its layer kinds
(:attr:`KimiLinearLM.mixer_kinds`) from them; a model of fewer layers than
the lists name (one pipeline stage's) builds the layers it has and leaves
the further entries to the further stages.  The blocks are
:class:`TransformerBlock`s whose token mixer and MLP are built here, and
everything else (embedding, forward, the slot cache of whole state beside a
headless latent, the pool programs' two methods, ``generate``) is
:class:`TransformerLM`'s.  ``num_nextn_predict_layers`` is 0 in the
published configuration: there is no multi-token-prediction module to build.
"""

from __future__ import annotations

from .. import nn
from .transformer import TransformerBlock, TransformerLM, _make_norm

__all__ = ["KimiLinearLM"]


def _layer_list(layers) -> set:
    """A published list of 1-based layer numbers, or its comma-separated
    text (``"1,2,3,5"``: how a configuration file whose harness hands a
    factory scalars alone carries a list)."""
    if isinstance(layers, str):
        layers = layers.split(",")
    return {int(i) for i in layers}


class KimiLinearLM(TransformerLM):
    """Args are the published configuration's, under this repo's names
    (chipbench/configs/kimi-linear-48b-a3b-serve.json maps them).

    ``num_experts`` is the ROUTER's width and ``moe_top_k`` its picks a
    token; ``experts_held`` / ``expert_offset`` say which of those experts'
    weights this model holds (0 = all): one chip's share of an
    expert-parallel deployment (nn/moe.py).  ``vocab_size`` may likewise be
    a slice of the published vocabulary: a smaller vocabulary.
    ``kda_layers`` / ``full_attn_layers`` are the published lists, as
    sequences or as comma-separated text (:func:`_layer_list`)."""

    def __init__(self, vocab_size: int, dim: int, depth: int,
                 num_heads: int, kv_lora_rank: int, qk_nope_head_dim: int,
                 qk_rope_head_dim: int, v_head_dim: int, dense_hidden: int,
                 kda_layers, full_attn_layers, linear_num_heads: int = 32,
                 linear_head_dim: int = 128, linear_conv_kernel: int = 4,
                 q_lora_rank=None, mla_use_nope: bool = True,
                 first_k_dense_replace: int = 1, moe_layer_freq: int = 1,
                 num_experts: int = 256, moe_top_k: int = 8,
                 moe_hidden: int = 1024, num_shared_experts: int = 1,
                 moe_renormalize: bool = True,
                 routed_scaling_factor: float = 1.0,
                 moe_router_activation_func: str = "sigmoid",
                 num_expert_group: int = 1, topk_group: int = 1,
                 experts_held: int = 0, expert_offset: int = 0,
                 rope_theta: float = 10000.0, norm_eps: float = 1e-5,
                 max_seq_len: int = 1048576):
        nn.Module.__init__(self)
        if num_expert_group != 1 or topk_group != 1:
            raise NotImplementedError(
                f"group-limited routing (num_expert_group "
                f"{num_expert_group}, topk_group {topk_group}) is not built: "
                f"the published Kimi Linear routes over one group")
        kda, full = _layer_list(kda_layers), _layer_list(full_attn_layers)
        wrong = [i + 1 for i in range(depth)
                 if (i + 1 in kda) == (i + 1 in full)]
        if wrong:
            raise ValueError(
                f"layers {wrong} (1-based) are not in exactly one of "
                f"kda_layers {sorted(kda)} and full_attn_layers "
                f"{sorted(full)}")
        self.num_experts = num_experts
        #: ``"kda"`` or ``"full_attention"`` per layer (the published lists)
        self.mixer_kinds = ["kda" if i + 1 in kda else "full_attention"
                            for i in range(depth)]
        #: ``"dense"`` or ``"moe"`` per layer (the published rule)
        self.layer_kinds = [
            "moe" if i >= first_k_dense_replace and i % moe_layer_freq == 0
            else "dense" for i in range(depth)]

        def mixer(kind):
            if kind == "kda":
                return nn.KimiDeltaAttention(
                    dim, linear_num_heads, linear_head_dim,
                    conv_kernel=linear_conv_kernel, eps=norm_eps)
            return nn.MultiheadLatentAttention(
                dim, num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, rope_theta=rope_theta,
                norm_eps=norm_eps, use_nope=mla_use_nope)

        def mlp(kind):
            if kind == "dense":
                return nn.GatedMLP(dim, dense_hidden)
            return nn.MoELayer(
                dim, num_experts, hidden=moe_hidden, top_k=moe_top_k,
                normalize_gates=moe_renormalize, dispatch="dropless",
                gated=True, shared_hidden=num_shared_experts * moe_hidden,
                shared_gate=False, scoring=moe_router_activation_func,
                selection_bias=True, routed_scale=routed_scaling_factor,
                experts_held=experts_held, expert_offset=expert_offset)

        blocks = [TransformerBlock(
            dim, num_heads, norm="rmsnorm", norm_eps=norm_eps,
            mixer=mixer(mix), mlp=mlp(kind))
            for mix, kind in zip(self.mixer_kinds, self.layer_kinds)]
        self._assemble(vocab_size, dim, max_seq_len, blocks,
                       ln_f=_make_norm("rmsnorm", dim, norm_eps),
                       head=nn.Linear(dim, vocab_size, bias=False),
                       learned_pos=False)

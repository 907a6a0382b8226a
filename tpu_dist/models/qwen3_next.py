"""Qwen3-Next — a hybrid decoder: Gated DeltaNet layers with a gated
full-attention layer every ``full_attention_interval``-th, a routed expert
layer with a shared expert in every block
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, ``config.json`` and
HF ``modeling_qwen3_next.py``; chipbench/reference/qwen3_next.py is the plain
form of the same equations).

    h = x + Mixer_i(N(x));  y = h + MoE(N(h));  final N;  untied head
    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)
    Mixer_i = full attention where (i + 1) % full_attention_interval == 0,
              else Gated DeltaNet

The layer pattern arrives as that ONE published scalar and the model derives
its list of layer kinds (:attr:`Qwen3NextLM.layer_kinds`) from it; the blocks
are :class:`TransformerBlock`s whose token mixer is built here, and
everything else (embedding, forward, the slot cache of two kinds of state,
the pool programs' two methods, ``generate``) is :class:`TransformerLM`'s.
The multi-token-prediction module of the model card is not in ``config.json``
and is not built.
"""

from __future__ import annotations

from .. import nn
from .transformer import TransformerBlock, TransformerLM, _make_norm

__all__ = ["Qwen3NextLM"]


class Qwen3NextLM(TransformerLM):
    """Args are the published configuration's, under this repo's names
    (chipbench/configs/qwen3-next-80b-a3b-serve.json maps them).

    ``num_experts`` is the ROUTER's width and ``moe_top_k`` its picks a
    token; ``experts_held`` / ``expert_offset`` say which of those experts'
    weights this model holds (0 = all): one chip's share of an
    expert-parallel deployment (nn/moe.py).  ``vocab_size`` may likewise be
    a slice of the published vocabulary: a smaller vocabulary."""

    def __init__(self, vocab_size: int, dim: int, depth: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 full_attention_interval: int = 4,
                 partial_rotary_factor: float = 0.25,
                 rope_theta: float = 1e7, norm_eps: float = 1e-6,
                 linear_key_heads: int = 16, linear_value_heads: int = 32,
                 linear_key_dim: int = 128, linear_value_dim: int = 128,
                 linear_conv_kernel: int = 4, num_experts: int = 512,
                 moe_top_k: int = 10, moe_hidden: int = 512,
                 shared_hidden: int = 512, moe_normalize_gates: bool = True,
                 experts_held: int = 0, expert_offset: int = 0,
                 max_seq_len: int = 262144):
        nn.Module.__init__(self)
        if full_attention_interval < 1:
            raise ValueError(f"full_attention_interval must be >= 1, got "
                             f"{full_attention_interval}")
        self.num_experts = num_experts
        #: ``"full_attention"`` or ``"linear_attention"`` per layer
        self.layer_kinds = [
            "full_attention" if (i + 1) % full_attention_interval == 0
            else "linear_attention" for i in range(depth)]

        def mixer(kind):
            if kind == "linear_attention":
                return nn.GatedDeltaNet(
                    dim, linear_key_heads, linear_value_heads,
                    linear_key_dim, linear_value_dim,
                    conv_kernel=linear_conv_kernel, eps=norm_eps)
            return nn.MultiheadSelfAttention(
                dim, num_heads, bias=False, causal=True, rope=True,
                rope_theta=rope_theta, qk_norm="head", qk_norm_eps=norm_eps,
                num_kv_heads=num_kv_heads, head_dim=head_dim,
                rotary_dim=int(head_dim * partial_rotary_factor), gated=True)

        blocks = [TransformerBlock(
            dim, num_heads, norm="rmsnorm_zc", norm_eps=norm_eps,
            mixer=mixer(kind),
            mlp=nn.MoELayer(dim, num_experts, hidden=moe_hidden,
                            top_k=moe_top_k,
                            normalize_gates=moe_normalize_gates,
                            dispatch="dropless", gated=True,
                            shared_hidden=shared_hidden,
                            experts_held=experts_held,
                            expert_offset=expert_offset))
            for kind in self.layer_kinds]
        self._assemble(vocab_size, dim, max_seq_len, blocks,
                       ln_f=_make_norm("rmsnorm_zc", dim, norm_eps),
                       head=nn.Linear(dim, vocab_size, bias=False),
                       learned_pos=False)

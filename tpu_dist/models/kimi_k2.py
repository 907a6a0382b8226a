"""Kimi K2 — the DeepSeek-V3 block: multi-head LATENT attention, leading
dense layers and then routed expert layers with a shared expert, scored by a
sigmoid with a selection bias
(https://huggingface.co/moonshotai/Kimi-K2-Instruct, ``config.json``,
``model_type: kimi_k2``, and the published ``modeling_deepseek.py``;
chipbench/reference/kimi_k2.py is the plain form of the same equations).

    h = x + Attn(N(x));  y = h + FFN_i(N(h));  final N;  untied head
    N(x) = x * rsqrt(mean(x^2) + eps) * w
    FFN_i = dense SwiGLU for i < first_k_dense_replace (and off the
            moe_layer_freq grid), else the expert layer
    Attn  = nn.MultiheadLatentAttention, rope by YaRN's blended frequencies,
            softmax scale (nope + rope)^-1/2 * mscale_all_dim's factor^2

Which layers are dense arrives as ONE published scalar
(``first_k_dense_replace``) and the model derives its list of layer kinds
(:attr:`KimiK2LM.layer_kinds`) from it, as :class:`Qwen3NextLM` derives its
own from ``full_attention_interval``; ``rope_scaling``'s entries arrive as
scalars named after their published keys.  The blocks are
:class:`TransformerBlock`s whose token mixer and MLP are built here, and
everything else (embedding, forward, the slot cache, the pool programs' two
methods, ``generate``) is :class:`TransformerLM`'s.
``num_nextn_predict_layers`` is 0 in the published configuration: there is
no multi-token-prediction module to build.
"""

from __future__ import annotations

from .. import nn
from .transformer import TransformerBlock, TransformerLM, _make_norm

__all__ = ["KimiK2LM"]


class KimiK2LM(TransformerLM):
    """Args are the published configuration's, under this repo's names
    (chipbench/configs/kimi-k2-serve.json maps them).

    ``num_experts`` is the ROUTER's width and ``moe_top_k`` its picks a
    token; ``experts_held`` / ``expert_offset`` say which of those experts'
    weights this model holds (0 = all): one chip's share of an
    expert-parallel deployment (nn/moe.py).  ``vocab_size`` may likewise be
    a slice of the published vocabulary: a smaller vocabulary."""

    def __init__(self, vocab_size: int, dim: int, depth: int,
                 num_heads: int, q_lora_rank: int, kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, dense_hidden: int,
                 first_k_dense_replace: int = 1, moe_layer_freq: int = 1,
                 num_experts: int = 384, moe_top_k: int = 8,
                 moe_hidden: int = 2048, n_shared_experts: int = 1,
                 moe_normalize_gates: bool = True,
                 routed_scaling_factor: float = 1.0,
                 scoring_func: str = "sigmoid",
                 topk_method: str = "noaux_tc", n_group: int = 1,
                 topk_group: int = 1, experts_held: int = 0,
                 expert_offset: int = 0, rope_theta: float = 50000.0,
                 rope_scaling_factor: float = 1.0,
                 rope_scaling_original_max_position_embeddings: int = 4096,
                 rope_scaling_beta_fast: float = 32.0,
                 rope_scaling_beta_slow: float = 1.0,
                 rope_scaling_mscale: float = 1.0,
                 rope_scaling_mscale_all_dim: float = 0.0,
                 norm_eps: float = 1e-6, max_seq_len: int = 131072,
                 residual=None):
        """``residual``: how a sublayer's output joins the residual, handed
        to every :class:`TransformerBlock` (None is ``x + f(x)``, the
        published Kimi K2; models/xing4.py builds on this constructor with
        a hyper-connection)."""
        nn.Module.__init__(self)
        if n_group != 1 or topk_group != 1:
            raise NotImplementedError(
                f"group-limited routing (n_group {n_group}, topk_group "
                f"{topk_group}) is not built: the published Kimi K2 routes "
                f"over one group")
        if topk_method not in ("noaux_tc", "greedy"):
            raise ValueError(f"topk_method must be 'noaux_tc' or 'greedy', "
                             f"got {topk_method!r}")
        self.num_experts = num_experts
        #: ``"dense"`` or ``"moe"`` per layer (the published rule)
        self.layer_kinds = [
            "moe" if i >= first_k_dense_replace and i % moe_layer_freq == 0
            else "dense" for i in range(depth)]
        # YaRN: blended frequencies for q_pe and k_pe; the cos and sin carry
        # mscale / mscale_all_dim (1 where the two are equal: unscaled), and
        # the softmax scale mscale_all_dim's factor squared
        inv_freq = nn.yarn_inv_freq(
            qk_rope_head_dim, rope_theta, rope_scaling_factor,
            rope_scaling_original_max_position_embeddings,
            rope_scaling_beta_fast, rope_scaling_beta_slow)
        all_dim = nn.yarn_mscale(rope_scaling_factor,
                                 rope_scaling_mscale_all_dim)
        if nn.yarn_mscale(rope_scaling_factor,
                          rope_scaling_mscale) != all_dim:
            raise NotImplementedError(
                "mscale != mscale_all_dim scales the rotary cos and sin, "
                "which is not built: the published Kimi K2 sets both to 1")
        scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5 * all_dim ** 2

        def mlp(kind):
            if kind == "dense":
                return nn.GatedMLP(dim, dense_hidden)
            return nn.MoELayer(
                dim, num_experts, hidden=moe_hidden, top_k=moe_top_k,
                normalize_gates=moe_normalize_gates, dispatch="dropless",
                gated=True, shared_hidden=n_shared_experts * moe_hidden,
                shared_gate=False, scoring=scoring_func,
                selection_bias=topk_method == "noaux_tc",
                routed_scale=routed_scaling_factor,
                experts_held=experts_held, expert_offset=expert_offset)

        blocks = [TransformerBlock(
            dim, num_heads, norm="rmsnorm", norm_eps=norm_eps,
            mixer=nn.MultiheadLatentAttention(
                dim, num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, rope_theta=rope_theta,
                rope_inv_freq=inv_freq, softmax_scale=scale,
                norm_eps=norm_eps),
            mlp=mlp(kind), residual=residual) for kind in self.layer_kinds]
        self._assemble(vocab_size, dim, max_seq_len, blocks,
                       ln_f=_make_norm("rmsnorm", dim, norm_eps),
                       head=nn.Linear(dim, vocab_size, bias=False),
                       learned_pos=False)

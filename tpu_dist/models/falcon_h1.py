"""Falcon-H1 — a hybrid decoder whose EVERY layer runs a Mamba-2 state-space
mixer and a grouped-query attention mixer SIDE BY SIDE on one normalised
input, with muP multipliers as constants of the program
(https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct, ``config.json``,
``model_type: falcon_h1``; the Falcon-H1 report and the published
``modeling_falcon_h1.py``; chipbench/reference/falcon_h1.py is the plain form
of the same equations).

    x0 = E[tokens] * embedding_multiplier
    u  = N(x)
    h  = x + attention_out_multiplier * Attn(u * attention_in_multiplier)
           + ssm_out_multiplier * SSM(u * ssm_in_multiplier)
    y  = h + MLP(N(h));  final N;  logits = (N(x_L) W_head) * lm_head_multiplier
    N(x) = x * rsqrt(mean(x^2) + eps) * w
    Attn: grouped queries, rope by halves over the whole head, keys scaled
          by key_multiplier (nn.MultiheadSelfAttention)
    SSM:  nn.Mamba2, its input projection's five segments scaled by
          ssm_multipliers
    MLP:  down(up(a) * silu(gate(a) * mlp_multipliers[0])) * mlp_multipliers[1]

Every layer is alike.  The blocks are :class:`TransformerBlock`s whose token
mixer is an :class:`nn.ParallelMixer` of the two (each branch owns its
parameters and its slot-cache entry under its own path,
``block3.attn.attention`` and ``block3.attn.ssm``: K/V columns AND a whole
state in one layer), and everything else (embedding, forward, the slot
cache, the pool programs' two methods, ``generate``) is
:class:`TransformerLM`'s.
"""

from __future__ import annotations

import numpy as np

from .. import nn
from .transformer import TransformerBlock, TransformerLM, _make_norm

__all__ = ["FalconH1LM"]


def _numbers(text, count: int, name: str) -> tuple:
    """A published list of ``count`` numbers, or its comma-separated text
    (how a configuration file whose harness hands a factory scalars alone
    carries a list)."""
    if isinstance(text, str):
        text = text.split(",")
    numbers = tuple(float(n) for n in text)
    if len(numbers) != count:
        raise ValueError(f"{name} holds {count} numbers, got {numbers!r}")
    return numbers


class FalconH1LM(TransformerLM):
    """Args are the published configuration's, under this repo's names
    (chipbench/configs/falcon-h1-34b-serve.json maps them).
    ``ssm_multipliers`` (five, ``[z | x | B | C | dt]``) and
    ``mlp_multipliers`` (two, gate and output) are the published lists, as
    sequences or as comma-separated text (:func:`_numbers`).  The
    multipliers are constants of the program, applied where the published
    code applies them; :meth:`init` draws the matrices they scale
    accordingly.  ``vocab_size`` may be a slice of the published
    vocabulary: a smaller vocabulary."""

    def __init__(self, vocab_size: int, dim: int, depth: int,
                 num_heads: int, num_kv_heads: int, head_dim: int,
                 mlp_hidden: int, mamba_heads: int, mamba_head_dim: int,
                 mamba_state_dim: int, mamba_groups: int = 1,
                 mamba_inner_dim=None, mamba_conv_kernel: int = 4,
                 mamba_chunk_size: int = 128,
                 embedding_multiplier: float = 1.0,
                 lm_head_multiplier: float = 1.0,
                 attention_in_multiplier: float = 1.0,
                 attention_out_multiplier: float = 1.0,
                 key_multiplier: float = 1.0,
                 ssm_in_multiplier: float = 1.0,
                 ssm_multipliers=(1.0, 1.0, 1.0, 1.0, 1.0),
                 ssm_out_multiplier: float = 1.0,
                 mlp_multipliers=(1.0, 1.0), rope_theta: float = 1e11,
                 norm_eps: float = 1e-5, max_seq_len: int = 262144,
                 attention_bias: bool = False, mlp_bias: bool = False,
                 projectors_bias: bool = False,
                 mamba_conv_bias: bool = True, mamba_proj_bias: bool = False,
                 mamba_rms_norm: bool = True,
                 mamba_norm_before_gate: bool = False):
        nn.Module.__init__(self)
        published = dict(attention_bias=False, mlp_bias=False,
                         projectors_bias=False, mamba_conv_bias=True, mamba_proj_bias=False,
                         mamba_rms_norm=True, mamba_norm_before_gate=False)
        given = dict(attention_bias=attention_bias, mlp_bias=mlp_bias,
                     projectors_bias=projectors_bias,
                     mamba_conv_bias=mamba_conv_bias,
                     mamba_proj_bias=mamba_proj_bias,
                     mamba_rms_norm=mamba_rms_norm,
                     mamba_norm_before_gate=mamba_norm_before_gate)
        if given != published:
            raise NotImplementedError(
                f"only the published Falcon-H1 layer is built ({published}),"
                f" got {given}")
        if mamba_inner_dim not in (None, mamba_heads * mamba_head_dim):
            raise ValueError(
                f"mamba_inner_dim {mamba_inner_dim} is not mamba_heads x "
                f"mamba_head_dim = {mamba_heads * mamba_head_dim}")
        self.num_experts = 0
        ssm = _numbers(ssm_multipliers, 5, "ssm_multipliers")
        gate_m, down_m = _numbers(mlp_multipliers, 2, "mlp_multipliers")

        def mixer():
            return nn.ParallelMixer(
                attention=(nn.MultiheadSelfAttention(
                    dim, num_heads, bias=False, causal=True, rope=True,
                    rope_theta=float(rope_theta), num_kv_heads=num_kv_heads,
                    head_dim=head_dim, key_multiplier=key_multiplier),
                    attention_in_multiplier, attention_out_multiplier),
                ssm=(nn.Mamba2(
                    dim, mamba_heads, mamba_head_dim, mamba_state_dim,
                    num_groups=mamba_groups, conv_kernel=mamba_conv_kernel,
                    chunk_size=mamba_chunk_size, eps=norm_eps,
                    multipliers=ssm),
                    ssm_in_multiplier, ssm_out_multiplier))

        blocks = [TransformerBlock(
            dim, num_heads, norm="rmsnorm", norm_eps=norm_eps, mixer=mixer(),
            mlp=nn.GatedMLP(dim, mlp_hidden, gate_multiplier=gate_m,
                            down_multiplier=down_m))
            for _ in range(depth)]
        self._assemble(vocab_size, dim, max_seq_len, blocks,
                       ln_f=_make_norm("rmsnorm", dim, norm_eps),
                       head=nn.Linear(dim, vocab_size, bias=False),
                       learned_pos=False,
                       embedding_multiplier=embedding_multiplier,
                       head_multiplier=lm_head_multiplier)

    def init(self, key):
        """The parameters from ``key``: every matrix drawn at the program's
        usual scale and then DIVIDED by the multiplier(s) the program
        applies to its product (``E`` by ``embedding_multiplier``, the
        head by ``lm_head_multiplier``, the fused attention projection by
        ``attention_in_multiplier`` and its keys' columns by
        ``key_multiplier`` besides, the five segments of the state-space
        input projection each by its ``ssm_multipliers`` entry times
        ``ssm_in_multiplier``, both output projections by their branch's
        out multiplier, ``gate`` and ``down`` by the
        ``mlp_multipliers``), so that with seeded weights every product
        has the size an unscaled layer's would: the multipliers are muP's
        way of keeping TRAINED weights at one scale, and a random matrix
        at that scale times 0.011 would flatten every softmax and mute a
        branch.  The model is unchanged: a loaded checkpoint brings its
        own matrices."""
        params = super().init(key)

        def divide(path, name, by):
            params[path][name] = params[path][name] / by

        divide("tok", "weight", self.embedding_multiplier)
        divide("head", "weight", self.head_multiplier)
        for i in range(self.depth):
            block = getattr(self, f"block{i}")
            mixer, path = block.attn, f"block{i}.attn"
            attention, ssm = mixer.attention, mixer.ssm
            (attn_in, attn_out), (ssm_in, ssm_out) = (
                mixer.branches["attention"], mixer.branches["ssm"])
            # the fused projection's columns, [q | k | v]
            divide(f"{path}.attention", "qkv_weight", attn_in * np.repeat(
                np.float32([1.0, attention.key_multiplier, 1.0]),
                [attention.q_dim, attention.kv_dim, attention.kv_dim]))
            divide(f"{path}.attention", "out_weight", attn_out)
            divide(f"{path}.ssm", "in_weight", ssm_in * np.repeat(
                np.float32(ssm.multipliers), ssm._segments))
            divide(f"{path}.ssm", "out_weight", ssm_out)
            divide(f"block{i}.mlp.gate", "weight", block.mlp.gate_multiplier)
            divide(f"block{i}.mlp.down", "weight", block.mlp.down_multiplier)
        return params

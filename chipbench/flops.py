"""Operations and bytes the algorithms need, computed from shapes.

"Needed" means by the mathematics, not by an implementation: causal attention
is charged half of T^2, recomputation (flash backward's second look at the
scores, remat) is not counted, and a padded tile costs nothing.  A matmul of
(m, k) x (k, n) is 2*m*k*n operations.
"""

from __future__ import annotations


def dense_lm_train(model_kwargs: dict, seq_len: int) -> float:
    """Forward + backward operations per token of a dense pre-LN decoder LM
    with a 4x MLP and an untied head (``tpu_dist.models:TransformerLM``).

    6 x the parameters that sit in matmuls (per block 3d^2 qkv + d^2 out +
    8d^2 MLP; the d x vocab head; embeddings are lookups and count nothing)
    plus attention: QK^T and PV are 2*T*d each per token and layer forward,
    halved because a causal token attends to half the sequence on average,
    times 3 for forward and backward: 6 * L * d * T."""
    d, layers = model_kwargs["dim"], model_kwargs["depth"]
    matmul_params = layers * 12 * d * d + d * model_kwargs["vocab_size"]
    return 6.0 * matmul_params + 6.0 * layers * d * seq_len


def flash_attention(batch: int, heads: int, seq: int, head_dim: int,
                    causal: bool = True, itemsize: int = 2) -> dict:
    """One attention call over (batch, seq, heads, head_dim), forward and
    backward, as operations and HBM bytes.

    Forward: QK^T and PV, 2 * 2*T*T*D per head.  Backward: dV, dP, dQ, dK,
    4 * 2*T*T*D per head (the recomputed scores are not needed work).
    Bytes: forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO
    and writes dQ, dK, dV; the f32 log-sum-exp row is written once and read
    once."""
    pairs = seq * seq / 2.0 if causal else float(seq * seq)
    per_matmul = 2.0 * batch * heads * pairs * head_dim
    tensor = batch * heads * seq * head_dim * itemsize
    lse = batch * heads * seq * 4
    return {"fwd_flops": 2 * per_matmul, "bwd_flops": 4 * per_matmul,
            "fwd_bytes": 4 * tensor + lse, "bwd_bytes": 8 * tensor + lse}


def fused_cross_entropy(rows: int, vocab: int, itemsize: int = 2) -> dict:
    """Softmax cross-entropy over (rows, vocab) logits, forward and backward.

    Forward reads the logits once and writes one f32 loss per row; backward
    reads them again and writes a gradient of the same shape and type.  Per
    element forward needs a max, a subtract, an exp and an add (4); backward
    a subtract, an exp, a divide, a label subtract and a scale (5).  Memory
    bound by a wide margin on any chip."""
    elems = float(rows) * vocab
    return {"fwd_flops": 4 * elems, "bwd_flops": 5 * elems,
            "fwd_bytes": elems * itemsize + rows * 8,
            "bwd_bytes": 2 * elems * itemsize + rows * 8}


def roofline(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = nbytes / peak["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))

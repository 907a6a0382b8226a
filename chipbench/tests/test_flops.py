"""chipbench.flops and peaks.json: the arithmetic by hand."""

import json
import os

import pytest

from chipbench import flops, spec

GPT2_MEDIUM = dict(vocab_size=50257, dim=1024, depth=24, num_heads=16,
                   max_seq_len=1024)


def test_gpt2_medium_flops_per_token_by_hand():
    # per block: qkv 3d^2 + out d^2 + MLP 2 * 4d^2 = 12 d^2
    matmul_params = 24 * 12 * 1024 * 1024 + 1024 * 50257
    assert matmul_params == 353_453_056
    attention = 6 * 24 * 1024 * 1024          # 6 * L * d * T, causal
    want = 6 * matmul_params + attention
    assert flops.dense_lm_train(GPT2_MEDIUM, 1024) == want
    assert want == pytest.approx(2.27e9, rel=2e-3)   # "2.27 GFLOP a token"
    # a step of 8 x 1024 tokens: no less than ~94 ms at 197 TFLOP/s
    assert 8192 * want / 197e12 == pytest.approx(0.0945, rel=1e-2)


def test_causal_attention_is_half_of_t_squared():
    full = flops.flash_attention(8, 16, 1024, 64, causal=False)
    half = flops.flash_attention(8, 16, 1024, 64, causal=True)
    assert half["fwd_flops"] == full["fwd_flops"] / 2
    assert half["bwd_flops"] == full["bwd_flops"] / 2
    # forward: QK^T and PV, 2*T*T*D each, per head and sequence
    assert full["fwd_flops"] == 8 * 16 * 2 * (2 * 1024 * 1024 * 64)
    assert full["bwd_flops"] == 2 * full["fwd_flops"]
    # the model's attention term is the same count: 3 * fwd over B*T tokens
    per_token = 3 * half["fwd_flops"] / (8 * 1024)
    assert per_token == 6 * 1024 * 1024        # 6 * d * T for one layer


def test_flash_and_ce_bytes():
    a = flops.flash_attention(8, 16, 1024, 64, itemsize=2)
    tensor = 8 * 16 * 1024 * 64 * 2
    lse = 8 * 16 * 1024 * 4
    assert a["fwd_bytes"] == 4 * tensor + lse      # Q K V in, O out
    assert a["bwd_bytes"] == 8 * tensor + lse      # Q K V O dO in, dQ dK dV out
    c = flops.fused_cross_entropy(8192, 50257, itemsize=2)
    logits = 8192 * 50257 * 2
    assert c["fwd_bytes"] == logits + 8192 * 8
    assert c["bwd_bytes"] == 2 * logits + 8192 * 8


def test_roofline_says_which_bound():
    peak = spec.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9 and peak["hbm_bytes"] == 16e9
    t, bound = flops.roofline(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")
    c = flops.fused_cross_entropy(8192, 50257)
    assert flops.roofline(c["fwd_flops"], c["fwd_bytes"], peak)[1] == "memory"


def test_unknown_device_is_an_error_not_a_default():
    with pytest.raises(SystemExit, match="no peaks for device_kind 'cpu'"):
        spec.peaks("cpu")
    table = json.load(open(os.path.join(spec.ROOT, "chipbench", "peaks.json")))
    assert all(row["source"] for row in table.values())

"""Xing4.0 (the DeepSeek-V3 block inside a residual of four streams mixed by
manifold-constrained hyper-connections) through the slot engine, on the CPU
at small sizes with seeded weights (ISSUE 38).

``nn.HyperConnection`` against the plain reference's lines
(chipbench/reference/xing4.py) and the Sinkhorn's rows and columns; YaRN's
blend at the published scalars; the program's float32 logits against the
reference for a full forward and for prefill + decode through a slot pool;
the absorbed path against the expanded one with the streams around both; the
engine end to end; the latent (and no stream) through the prefix cache, the
K/V mover and the disaggregated engine, for Kimi K2 and Xing4 alike;
``stats()["residual"]`` against hand arithmetic.
"""

import importlib.util
import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn, serve
from tpu_dist.models import KimiK2LM, TransformerBlock, TransformerLM, Xing4LM

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dict(vocab_size=211, hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
           intermediate_size=96, first_k_dense_replace=1, moe_layer_freq=1,
           n_routed_experts=8, router_num_experts=8, expert_offset=0,
           num_experts_per_tok=2, moe_intermediate_size=32,
           n_shared_experts=1, norm_topk_prob=True,
           routed_scaling_factor=2, scoring_func="sigmoid",
           topk_method="noaux_tc", n_group=1, topk_group=1,
           rope_theta=10000, rms_norm_eps=1e-6,
           rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                             mscale_all_dim=1,
                             original_max_position_embeddings=16,
                             type="yarn"),
           # 3 steps, not the published 20: every program of this file
           # compiles the unrolled iteration 4 times on the CPU (2 to 3 times
           # the file's time at 20); the module's own tests run all 20
           hc_mult=4, hc_sinkhorn_iters=3, hc_eps=1e-6,
           mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
           max_position_embeddings=128)
#: the published rope scalars of Xing4.0-29B-A4B
PUBLISHED_ROPE = dict(qk_rope_head_dim=64, rope_theta=10000, rope_scaling=dict(
    beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
    original_max_position_embeddings=4096, type="yarn"))
ATOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "xing4_reference", os.path.join(ROOT, "chipbench", "reference",
                                        "xing4.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _model(cfg=CFG, factory=Xing4LM, **over):
    sc = cfg["rope_scaling"]
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              depth=cfg["num_hidden_layers"],
              num_heads=cfg["num_attention_heads"],
              q_lora_rank=cfg["q_lora_rank"],
              kv_lora_rank=cfg["kv_lora_rank"],
              qk_nope_head_dim=cfg["qk_nope_head_dim"],
              qk_rope_head_dim=cfg["qk_rope_head_dim"],
              v_head_dim=cfg["v_head_dim"],
              dense_hidden=cfg["intermediate_size"],
              first_k_dense_replace=cfg["first_k_dense_replace"],
              moe_layer_freq=cfg["moe_layer_freq"],
              num_experts=cfg["router_num_experts"],
              experts_held=cfg["n_routed_experts"],
              expert_offset=cfg["expert_offset"],
              moe_top_k=cfg["num_experts_per_tok"],
              moe_hidden=cfg["moe_intermediate_size"],
              n_shared_experts=cfg["n_shared_experts"],
              moe_normalize_gates=cfg["norm_topk_prob"],
              routed_scaling_factor=cfg["routed_scaling_factor"],
              scoring_func=cfg["scoring_func"],
              topk_method=cfg["topk_method"], n_group=cfg["n_group"],
              topk_group=cfg["topk_group"], rope_theta=cfg["rope_theta"],
              rope_scaling_factor=sc["factor"],
              rope_scaling_original_max_position_embeddings=sc[
                  "original_max_position_embeddings"],
              rope_scaling_beta_fast=sc["beta_fast"],
              rope_scaling_beta_slow=sc["beta_slow"],
              rope_scaling_mscale=sc["mscale"],
              rope_scaling_mscale_all_dim=sc["mscale_all_dim"],
              norm_eps=cfg["rms_norm_eps"],
              max_seq_len=cfg["max_position_embeddings"])
    if factory is Xing4LM:
        kw.update({k: cfg[k] for k in (
            "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max")})
    return factory(**dict(kw, **over))


def _perturbed(params, seed=7):
    """Norm weights and scales start at one: perturb every vector and
    scalar (the hyper-connections' too) so a wrong mapping shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim <= 1 else a
        for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def served():
    """The model, its parameters and a 40-token sequence with the
    reference's logits."""
    model = _model()
    params = _perturbed(model.init(jax.random.key(0)))
    seq = np.asarray(jax.random.randint(jax.random.key(1), (40,), 0, 211))
    want = np.asarray(jax.jit(lambda p, t: REF.forward(
        CFG, REF.stack_params(CFG, p), t))(params, seq[None])[0])
    return model, params, seq, want


# -- the hyper-connection -------------------------------------------------------

def _hc(iters=20, dim=32, n=4, seed=0):
    hc = nn.HyperConnection(dim, n, sinkhorn_iters=iters)
    params = _perturbed(hc.init(jax.random.key(seed)))
    xs = tuple(jax.random.normal(k, (2, 5, dim))
               for k in jax.random.split(jax.random.key(seed + 1), n))
    cfg = dict(CFG, hc_sinkhorn_iters=iters)
    return hc, params, xs, cfg


def _apply(module, params, fn):
    """``fn()`` inside ``module``'s apply context (an entry point other
    than ``forward``)."""
    from tpu_dist.nn.module import _Context, _stack
    module._assign_paths()
    _stack().append(_Context(params, None, False, None))
    try:
        return fn()
    finally:
        _stack().pop()


def _coefficients(hc, params, xs):
    """(B, T, n), (B, T, n) and (B, T, n, n), as the reference shapes
    them."""
    pre, post, res = _apply(hc, params, lambda: hc.coefficients(xs))
    stack = lambda rows: jnp.concatenate(rows, axis=-1)
    return (stack(pre), stack(post),
            jnp.stack([stack(r) for r in res], axis=-2))


def test_the_hyper_connection_is_the_references_lines():
    """Coefficients, the sublayer's input and the streams after it, against
    chipbench/reference/xing4.py on the same parameters, to 1e-5."""
    hc, params, xs, cfg = _hc()
    stacked = jnp.stack(xs, axis=-2)                      # (B, T, n, C)
    want = jax.jit(lambda p, x: REF.hyper_coefficients(cfg, p, x))(
        params[""], stacked)
    got = jax.jit(lambda p, xs: _coefficients(hc, p, xs))(params, xs)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5)
    f = lambda u: jnp.tanh(u) * 3.0 + 1.0                 # any sublayer
    @jax.jit
    def both(params, xs):
        u, coeff = hc.apply(params, xs)
        return u, _apply(hc, params, lambda: hc.post(xs, f(u), coeff))
    u, after = both(params, xs)
    np.testing.assert_allclose(
        u, jnp.einsum("...i,...ic->...c", want[0], stacked), atol=1e-5)
    assert isinstance(after, tuple) and len(after) == 4
    np.testing.assert_allclose(
        jnp.stack(after, axis=-2),
        jax.jit(lambda p, x: REF.hyper_connected(cfg, p, x, f))(
            params[""], stacked), atol=1e-5)
    # Hpre in (0, 1), Hpost in (0, 2), Hres positive and token by token
    assert 0 < float(got[0].min()) and float(got[0].max()) < 1
    assert 0 < float(got[1].min()) and float(got[1].max()) < 2
    assert float(got[2].min()) > 0
    assert float(jnp.abs(got[2][0, 0] - got[2][1, 3]).max()) > 1e-3


def _res(iters, **leaves):
    hc, params, xs, _ = _hc(iters)
    return _coefficients(hc, {"": dict(params[""], **leaves)}, xs)[2]


def test_twenty_sinkhorn_steps_make_it_doubly_stochastic_and_one_does_not():
    """At a unit scale of the logits and a bias of 2 I + U(+-0.5) 20 steps
    end within 1e-3 of doubly stochastic and 1 step does not; at the DRAWN
    parameters (scale 4, 3 I + U(+-1): the
    matrix starts far from it, so that the steps count in the served logits)
    20 steps leave the columns within 5e-2 where 1 leaves them 0.1 off."""
    unit = dict(res_scale=jnp.ones(()), res_bias=2.0 * jnp.eye(4) + 0.5
                * jax.random.uniform(jax.random.key(3), (4, 4), minval=-1.0))
    res = _res(20, **unit)
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-3)
    res1 = _res(1, **unit)
    np.testing.assert_allclose(res1.sum(-1), 1.0, atol=1e-3)   # rows last
    assert float(jnp.abs(res1.sum(-2) - 1.0).max()) > 1e-2
    drawn, drawn1 = _res(20), _res(1)
    np.testing.assert_allclose(drawn.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(drawn.sum(-2), 1.0, atol=5e-2)
    assert float(jnp.abs(drawn1.sum(-2) - 1.0).max()) > 0.1
    assert float(jnp.abs(drawn1 - drawn).max()) > 0.05
    # neither the identity nor uniform, and not one matrix for every token
    off = 1.0 - jnp.diagonal(drawn, axis1=-2, axis2=-1)
    assert 0.1 < float(off.mean()) < 0.6
    assert float(drawn.reshape(-1, 16).std(0).mean()) > 0.05


def test_the_clamp_bounds_the_exponential():
    hc, params, xs, cfg = _hc()
    wild = dict(params[""], res_bias=params[""]["res_bias"].at[0, 0].set(500.0)
                .at[1, 2].set(-500.0))
    res = _coefficients(hc, {"": wild}, xs)[2]
    assert bool(jnp.isfinite(res).all())
    np.testing.assert_allclose(
        res, REF.hyper_coefficients(cfg, wild, jnp.stack(xs, -2))[2],
        atol=1e-5)
    with pytest.raises(ValueError, match="at least 2 streams"):
        nn.HyperConnection(32, 1)


def test_the_sinkhorn_lowers_to_no_loop_and_no_reduction():
    """Unrolled, its sums products with constant matrices: the lowered text
    of a decode step's worth of coefficients holds no ``while``, of
    ``reduce`` only the norm's sum of squares a stream, and a division a
    half step."""
    hc, params, _, _ = _hc()
    xs = tuple(jnp.zeros((6, 1, 32)) for _ in range(4))
    text = jax.jit(lambda p, xs: hc.apply(p, xs)).lower(params, xs).as_text()
    assert "stablehlo.while" not in text
    assert len([l for l in text.splitlines() if "stablehlo.reduce" in l]) == 4
    assert text.count("stablehlo.divide") >= 2 * 20
    assert text.count("stablehlo.dot_general") >= 4 + 2 * 20


# -- YaRN at the published scalars ----------------------------------------------

def test_yarn_blends_between_pair_10_and_pair_23():
    """rope 64, theta 10,000, original 4,096, betas 32 and 1: the pair that
    turns 32 times within 4,096 positions is 64 ln(4096 / 64 pi) / (2 ln
    10000) = 10.47 and the one that turns once 22.5, so pairs 0-10 keep
    their frequency, 23-31 take a 64th, 11-22 the linear blend."""
    c = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(1e4))
    assert 10.4 < c(32) < 10.5 and 22.4 < c(1) < 22.6
    low, high = math.floor(c(32)), math.ceil(c(1))
    assert (low, high) == (10, 23)
    f = nn.yarn_inv_freq(64, 10000, 64, 4096, 32, 1)
    own = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(f[:11], own[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], own[23:] / 64, rtol=1e-6)
    ramp = (np.arange(11, 23) - low) / (high - low)
    np.testing.assert_allclose(
        f[11:23], own[11:23] * (1 - ramp) + own[11:23] / 64 * ramp,
        rtol=1e-5)
    assert np.all(f[11:23] < own[11:23]) and np.all(f[11:23] > own[11:23] / 64)
    # the reference computes them apart and agrees
    np.testing.assert_allclose(
        f, REF.yarn_inv_freq(dict(CFG, **PUBLISHED_ROPE)), rtol=1e-6)
    # the softmax scale: 192^-1/2 (0.1 ln 64 + 1)^2 = 0.1447
    big = _model(qk_nope_head_dim=128, qk_rope_head_dim=64,
                 rope_scaling_original_max_position_embeddings=4096)
    attn = big.block0.attn
    assert attn.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2, rel=1e-6)
    assert attn.softmax_scale == pytest.approx(0.1447, abs=5e-5)
    np.testing.assert_allclose(attn.rope_inv_freq, f)


# -- the model against the reference --------------------------------------------

def test_the_blocks_are_transformer_blocks_with_two_hyper_connections():
    model = _model()
    assert model.layer_kinds == ["dense", "moe"] and model.streams == 4
    for i in range(2):
        blk = getattr(model, f"block{i}")
        assert type(blk) is TransformerBlock and blk.streams == 4
        assert isinstance(blk.hc_attn, nn.HyperConnection)
        assert isinstance(blk.hc_mlp, nn.HyperConnection)
        assert blk.hc_attn is not blk.hc_mlp
        assert isinstance(blk.attn, nn.MultiheadLatentAttention)
    params = model.init(jax.random.key(0))
    hc = params["block1.hc_mlp"]
    assert {k: v.shape for k, v in hc.items()} == {
        "norm_weight": (256,), "pre_weight": (256, 4),
        "post_weight": (256, 4), "res_weight": (256, 16), "pre_scale": (),
        "post_scale": (), "res_scale": (), "pre_bias": (4,),
        "post_bias": (4,), "res_bias": (4, 4)}
    assert sum(v.size for v in hc.values()) == 256 * 24 + 256 + 27
    assert not jnp.array_equal(hc["res_weight"],
                               params["block1.hc_attn"]["res_weight"])
    # everything else is Kimi K2's, leaf for leaf
    kimi = _model(factory=KimiK2LM).init(jax.random.key(0))
    assert set(params) - set(kimi) == {f"block{i}.hc_{s}" for i in range(2)
                                       for s in ("attn", "mlp")}
    assert all(set(params[k]) == set(kimi[k]) for k in kimi)
    assert model.residual_numbers_per_row() == 4 * 14 * 64
    assert _model(factory=KimiK2LM).residual_numbers_per_row() == 0
    assert _model(factory=KimiK2LM).streams == 1


def test_the_seeded_weights_are_the_assumed_ones():
    """``Xing4LM.init`` (the configuration's ``assumed``): the embedding at a
    deviation of 0.3 where Kimi K2 keeps ``nn.Embedding``'s 1, a layer's
    routed experts one common draw plus a sixteenth of a draw of their own
    where Kimi K2's are independent draws, and the drawn hyper-connections'
    scales and biases."""
    from tpu_dist.models.xing4 import EMBEDDING_STD, EXPERT_DEVIATION
    params = _model().init(jax.random.key(5))
    kimi = _model(factory=KimiK2LM).init(jax.random.key(5))
    np.testing.assert_allclose(params["tok"]["weight"],
                               EMBEDDING_STD * kimi["tok"]["weight"])
    assert abs(float(kimi["tok"]["weight"].std()) - 1.0) < 0.05
    for name, fan_in in (("w1", 64), ("w3", 64), ("w2", 32)):
        w = params["block1.mlp"][name]
        unit = 1.0 / math.sqrt(3 * fan_in)      # U(+-1/sqrt(fan_in))'s
        common, own = w.mean(0), w - w.mean(0)
        assert abs(float(common.std()) / unit - 1.0) < 0.1
        assert abs(float(own.std()) / (EXPERT_DEVIATION * unit) - 1.0) < 0.1
        assert (kimi["block1.mlp"][name].shape == w.shape
                and abs(float(kimi["block1.mlp"][name].std()) / unit - 1.0)
                < 0.1)
    assert set(params["block1.mlp"]) == set(kimi["block1.mlp"])
    hc = params["block1.hc_mlp"]
    assert (float(hc["pre_scale"]), float(hc["post_scale"]),
            float(hc["res_scale"])) == (1.0, 3.0, 4.0)
    assert float(jnp.abs(hc["post_bias"]).max()) <= 3.0
    off = hc["res_bias"] - 3.0 * jnp.eye(4)
    assert float(jnp.abs(off).max()) <= 1.0 and float(jnp.abs(off).max()) > 0.5


@pytest.fixture(scope="module")
def forward(served):
    """The served model's full forward, compiled once for the module."""
    return jax.jit(served[0].apply)


def test_full_forward_is_the_references(served, forward):
    _, params, seq, want = served
    got = forward(params, seq[None])
    assert got.shape == (1, 40, 211)
    np.testing.assert_allclose(got[0], want, atol=ATOL)


@pytest.mark.parametrize("fault", ["one_step", "hpost_without_its_2",
                                   "one_stream", "no_clamp"])
def test_the_comparison_sees_a_fault_in_the_residuals_mathematics(
        fault, served, forward):
    """The reference with one fault planted differs from the program by far
    more than ATOL (on the chip's seeded weights the cell's ``logit_tol``
    sees the first three too: PERF.md section 6, PR 38)."""
    _, params, seq, right = served
    if fault == "no_clamp":
        # the Sinkhorn normalises a large entry away whether it was clamped
        # at e^30 or not: the clamp shows only where float32's exponential
        # overflows (a logit past 88), as not-a-number
        params = dict(params, **{"block1.hc_attn": dict(
            params["block1.hc_attn"],
            res_bias=params["block1.hc_attn"]["res_bias"].at[0, 1].set(
                100.0))})
        right = REF.forward(CFG, REF.stack_params(CFG, params), seq[None])[0]
    got = forward(params, seq[None])[0]
    faulty = _reference()
    cfg = dict(CFG)
    if fault == "one_step":
        cfg["hc_sinkhorn_iters"] = 1
    elif fault == "no_clamp":
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"] = -1e9, 1e9
    else:
        plain = faulty.hyper_coefficients

        def planted(config, p, xs):
            if fault == "one_stream":
                xs = jnp.broadcast_to(xs[..., :1, :], xs.shape)
            pre, post, res = plain(config, p, xs)
            return pre, post / 2 if fault == "hpost_without_its_2" else post, res
        faulty.hyper_coefficients = planted
    wrong = faulty.forward(cfg, faulty.stack_params(cfg, params), seq[None])[0]
    np.testing.assert_allclose(got, right, atol=ATOL)
    assert bool(jnp.isfinite(got).all())
    assert not float(jnp.abs(got - wrong).max()) <= 100 * ATOL


def test_prefill_then_decode_steps_are_the_references_full_forward(served):
    """Prefill 25 tokens into slot 1 of a 3-slot pool (bucket 32), then 15
    decode steps with the other slots free: logits, not tokens."""
    model, params, seq, want = served
    pool = model.init_slot_cache(3, 64)
    moe = model.init_moe_counters()
    prompt = np.zeros(32, np.int32)
    prompt[:25] = seq[:25]
    logits, pool, moe = jax.jit(model.prefill_into_slot)(
        params, prompt, 25, 1, pool, moe)
    np.testing.assert_allclose(logits, want[24], atol=ATOL)
    step = jax.jit(model.decode_step)
    lengths = np.array([0, 25, 0], np.int32)
    for i in range(25, 40):
        toks = np.array([0, seq[i], 0], np.int32)
        logits, pool, moe = step(params, toks, lengths, pool, moe)
        np.testing.assert_allclose(logits[1], want[i], atol=ATOL)
        lengths[1] += 1
    rows = sum(int(c["rows"].sum()) for c in jax.device_get(moe).values())
    assert rows == 1 * 2 * (25 + 15)


def test_absorbed_decode_equals_expanded_append_inside_the_streams(served):
    """One new token through the slot pool (a vector index: the absorbed
    path) against the same token appended at a scalar index (the expanded
    path), the hyper-connections around both: logits and the new latent
    column to 1e-5."""
    model, params, seq, _ = served
    prompt = np.zeros(32, np.int32)
    prompt[:25] = seq[:25]
    _, rows, _ = jax.jit(
        lambda p, toks: model.prefill_rows(p, toks, 25, 64))(params, prompt)
    pool = nn.cache.write_slot_rows(model.init_slot_cache(2, 64), rows, 1)
    absorbed, pool, _ = jax.jit(model.decode_step)(
        params, np.array([0, seq[25]], np.int32),
        np.array([0, 25], np.int32), pool)
    suffix = np.zeros(16, np.int32)
    suffix[0] = seq[25]
    expanded, again, _ = jax.jit(
        lambda p, toks, rows, hit: model.prefill_rows(
            p, toks, 26, 64, prefix_rows=rows, prefix_len=hit))(
        params, suffix, rows, np.int32(25))
    np.testing.assert_allclose(absorbed[1], expanded, atol=1e-5)
    for path in rows:
        np.testing.assert_allclose(pool[path]["latent"][1, :, :26],
                                   again[path]["latent"][0, :, :26],
                                   atol=1e-5)


def test_generate_opens_and_closes_the_streams_in_its_one_loop(served):
    model, params, seq, want = served
    out = jax.jit(lambda p, toks: model.generate(p, toks, 5))(
        params, jnp.asarray(seq[None, :30]))
    assert out.shape == (1, 35)
    assert int(out[0, 30]) == int(want[29].argmax())


def test_remat_keeps_the_one_loop_over_blocks():
    """The remat branch hands the tuple of streams through
    ``jax.checkpoint`` as it hands one array: same logits, and a gradient
    that reaches a hyper-connection (one layer, one Sinkhorn step: the
    backward pass compiles slowly on the CPU)."""
    model = _model(depth=1, first_k_dense_replace=0, hc_sinkhorn_iters=1)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (1, 8), 0, 211)
    fwd = lambda p: model.apply(p, toks, state={})[0]
    want = jax.jit(fwd)(params)
    model.remat = True
    try:
        np.testing.assert_allclose(jax.jit(fwd)(params), want, atol=1e-6)
        grads = jax.jit(jax.grad(lambda p: fwd(p).sum()))(params)
        assert float(jnp.abs(grads["block0.hc_attn"]["res_weight"]).max()) > 0
    finally:
        model.remat = False


# -- the engine -------------------------------------------------------------------

def test_the_engine_serves_the_references_tokens(served):
    """SlotEngine end to end, two requests sharing the pool: every served
    token is within 1e-4 of the largest logit of the reference's full
    forward over the sequence as served."""
    model, params, seq, _ = served
    eng = serve.SlotEngine(model, params, num_slots=2, max_len=64,
                           min_bucket=16)
    assert nn.cache.state_leaves(eng.cache) == []
    got = {0: [], 1: []}
    reqs = [serve.Request(seq[:n], max_new_tokens=6,
                          on_token=lambda r, t, i=i: got[i].append(int(t)))
            for i, n in enumerate((30, 20))]
    for r in reqs:
        eng.admit(r)
    while eng.active.any():
        eng.step()
    stacked = REF.stack_params(CFG, params)
    forward = jax.jit(lambda toks: REF.forward(CFG, stacked, toks[None])[0])
    for i, n in enumerate((30, 20)):
        assert len(got[i]) == 6
        full = np.zeros(40, np.int32)       # causal: the padding is unseen
        full[:n + 6] = np.concatenate([seq[:n], got[i]])
        logits = np.asarray(forward(full))
        for k, tok in enumerate(got[i]):
            row = logits[n - 1 + k]
            assert row.max() - row[tok] < 1e-4


def _hand_bytes(rows, sublayers=4, n=4, c=64, itemsize=4):
    return rows * sublayers * (3 * n + 2) * c * itemsize


def test_residual_stats_against_hand_arithmetic(served):
    """A row moves 14 C numbers a sublayer: X read and u written, X and y
    read and X' written.  Rows are the requests' (true prompt tokens, busy
    slots); padding and free slots are not counted and cost no byte."""
    model, params, seq, _ = served
    eng = serve.SlotEngine(model, params, num_slots=3, max_len=64,
                           min_bucket=16)
    zero = {"rows": 0, "bytes": 0}
    assert eng.stats()["residual"] == {"streams": 4, "sublayers": 4,
                                       "prefill": zero, "decode": zero}
    eng.admit(serve.Request(seq[:9], max_new_tokens=4))     # bucket 16
    eng.admit(serve.Request(seq[:12], max_new_tokens=2))    # bucket 16
    got = eng.stats()["residual"]
    assert got["prefill"] == {"rows": 21, "bytes": _hand_bytes(21)}
    assert _hand_bytes(1) == 4 * 14 * 64 * 4 == 14336
    eng.step()          # 2 busy rows of 3
    eng.step()          # 1 (the second request ended)
    got = eng.stats()["residual"]
    assert got["decode"] == {"rows": 3, "bytes": _hand_bytes(3)}
    eng.reset_stats()
    assert eng.stats()["residual"] == {"streams": 4, "sublayers": 4,
                                       "prefill": zero, "decode": zero}
    # bfloat16 streams move half the bytes
    half = serve.SlotEngine(model, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), params), num_slots=2, max_len=64,
        min_bucket=16)
    assert half._residual["row_bytes"] * 2 == eng._residual["row_bytes"]


def test_a_plain_residual_counts_rows_and_no_bytes():
    model = TransformerLM(97, dim=32, depth=2, num_heads=2, max_seq_len=32)
    eng = serve.SlotEngine(model, model.init(jax.random.key(0)), num_slots=2,
                           max_len=32)
    eng.admit(serve.Request([1, 2, 3], max_new_tokens=3))
    eng.step()
    got = eng.stats()["residual"]
    assert got["streams"] == 1 and got["sublayers"] == 4
    assert got["prefill"]["rows"] == 3 and got["decode"]["rows"] == 1
    assert got["prefill"]["bytes"] == got["decode"]["bytes"] == 0


# -- a whole prompt's attention through the flash forward kernel (ISSUE 39) ------

#: heads of 192 / 128 as published inside the four streams; a 1,024 bucket
WIDE = dict(CFG, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            num_attention_heads=2, max_position_embeddings=1024)


@pytest.fixture(scope="module")
def wide():
    """The model at WIDE, a prompt of 900 in a 1,024 bucket, the float32
    reference's logits at its last token, and ``prefill_rows`` under either
    ``attention_impl`` (the kernel interpreted)."""
    model = _model(WIDE)
    params = _perturbed(model.init(jax.random.key(0)))
    prompt = np.asarray(jax.random.randint(jax.random.key(1), (1024,), 0,
                                           211), np.int32)
    want = np.asarray(jax.jit(lambda p, t: REF.forward(
        WIDE, REF.stack_params(WIDE, p), t))(params, prompt[None, :900])[0])
    # a function of its own a branch: a trace is cached by the function
    # traced, and knows nothing of the attention_impl it was made under
    prefill = lambda: lambda p, x: model.prefill_rows(p, x, 900, 1024)
    got = {}
    for impl in ("dense", "flash"):
        with nn.attention_impl(impl):
            logits, rows, _ = jax.jit(prefill())(params, prompt)
        got[impl] = np.asarray(logits), jax.tree.map(np.asarray, rows)
    return model, params, prompt, want, got, prefill


def test_a_whole_prompt_on_the_kernel_is_the_dense_branchs(wide):
    _, _, _, want, got, _ = wide
    np.testing.assert_allclose(got["flash"][0], want[899], atol=ATOL)
    np.testing.assert_allclose(got["flash"][0], got["dense"][0], atol=ATOL)
    for path, entry in got["dense"][1].items():
        assert set(entry) == {"latent"}
        np.testing.assert_allclose(got["flash"][1][path]["latent"],
                                   entry["latent"], atol=ATOL)


def test_which_prefill_takes_the_kernel_and_what_its_program_holds(wide):
    """Decided by the call (a whole prompt from 0, 1,024 or more, heads of
    whole 64s, ``attention_impl``), whatever residual surrounds the layer;
    and on the kernel no equation of the prefill program, its sub-programs
    included, produces a ``t x t`` array: the scores are gone from the
    program, not only from one trace."""
    model, params, prompt, _, _, prefill = wide
    attn = model.block1.attn
    assert not attn.takes_prefill_kernel(1024, 0)       # the CPU's default
    assert model.prefill_attention_facts(1024)["kernel"] is False
    with nn.attention_impl("flash"):
        assert attn.takes_prefill_kernel(1024, 0)
        assert not attn.takes_prefill_kernel(512, 0)
        assert not attn.takes_prefill_kernel(1024, jnp.int32(0))
        assert not _model().block1.attn.takes_prefill_kernel(1024, 0)
        assert model.prefill_attention_facts(1024) == {
            "kernel": True, "heads": 4, "pairs_executed": 4 * 10 * 256 * 256}
        assert model.prefill_attention_facts(512) == {
            "kernel": False, "heads": 4, "pairs_executed": 4 * 512 * 512}

    def squares(jaxpr):
        """Shapes of every array an equation produces, sub-programs
        included, whose last two axes are both a bucket long."""
        found = []
        for eqn in jaxpr.eqns:
            found += [v.aval.shape for v in eqn.outvars
                      if len(getattr(v.aval, "shape", ())) >= 2
                      and min(v.aval.shape[-2:]) >= 1024]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += squares(sub)
        return found

    seen = {}
    for impl in ("dense", "flash"):
        with nn.attention_impl(impl):
            seen[impl] = squares(jax.make_jaxpr(prefill())(params,
                                                           prompt).jaxpr)
    assert (1, 2, 1024, 1024) in seen["dense"]
    assert seen["flash"] == []


def test_the_engine_serves_the_same_tokens_on_either_branch(wide):
    model, params, prompt, want, _, _ = wide
    served = {}
    for impl in ("dense", "flash"):
        with nn.attention_impl(impl):
            eng = serve.SlotEngine(model, params, num_slots=2, max_len=1024,
                                   min_bucket=1024)
            got = []
            eng.admit(serve.Request(prompt[:900], max_new_tokens=3,
                                    on_token=lambda r, t: got.append(t)))
            while eng.active.any():
                eng.step()
            served[impl] = got, eng.stats()["prefill_attn"]
    assert served["flash"][0] == served["dense"][0]
    assert served["flash"][0][0] == int(want[899].argmax())
    assert len(served["flash"][0]) == 3
    assert served["flash"][1]["kernel_prefills"] == 1
    assert served["dense"][1]["kernel_prefills"] == 0


# -- what is cached and moved: the latent, never a stream ------------------------

@pytest.fixture(scope="module", params=["kimik2", "xing4"])
def latent_model(request):
    """Both latent-attention models at the same sizes, with a prompt's
    prefilled rows: the movers below must not tell them apart."""
    model = _model(factory=KimiK2LM if request.param == "kimik2" else Xing4LM)
    params = model.init(jax.random.key(0))
    prompt = np.asarray(jax.random.randint(jax.random.key(2), (16,), 0, 211))
    _, rows, _ = jax.jit(
        lambda p, toks: model.prefill_rows(p, toks, 16, 16))(params, prompt)
    return model, params, prompt, jax.tree.map(np.asarray, rows)


def test_the_cache_holds_the_latent_and_no_stream(latent_model):
    model, params, prompt, rows = latent_model
    pool = model.init_slot_cache(3, 32, jnp.bfloat16)
    assert set(pool) == {f"block{i}.attn" for i in range(2)}
    # 16 + 8 numbers a position a layer, whatever the residual's width
    assert all(set(e) == {"latent"} and e["latent"].shape == (3, 24, 32)
               for e in pool.values())
    assert nn.cache.state_leaves(pool) == []
    assert nn.cache.slot_bytes(pool) == (0, 2 * 24 * 2)
    assert all(set(e) == {"latent"} and e["latent"].shape == (1, 24, 16)
               for e in rows.values())
    assert not any("hc_" in path for path in list(pool) + list(rows))


def test_the_prefix_cache_round_trips_prefilled_latent_rows(latent_model):
    model, params, prompt, rows = latent_model
    pc = serve.PrefixCache(block_tokens=4)
    assert pc.insert(prompt, rows, 16) == 4
    longer = np.concatenate([prompt, [7, 8, 9]])
    hit, got = pc.match(longer)
    assert hit == 16
    for path in rows:
        np.testing.assert_array_equal(got[path]["latent"],
                                      rows[path]["latent"])
    # the suffix prefilled over the cached columns is the whole prompt's
    suffix = np.zeros(16, np.int32)
    suffix[:3] = longer[16:]
    over, _, _ = jax.jit(
        lambda p, toks, rows, hit: model.prefill_rows(
            p, toks, 19, 32, prefix_rows=rows, prefix_len=hit))(
        params, suffix, nn.cache.pad_time(got, 32), np.int32(16))
    whole = np.zeros(32, np.int32)
    whole[:19] = longer
    want, _, _ = jax.jit(
        lambda p, toks: model.prefill_rows(p, toks, 19, 32))(params, whole)
    np.testing.assert_allclose(over, want, atol=1e-5)


def test_kv_transfer_round_trips_prefilled_latent_rows(latent_model):
    from tpu_dist.collectives.transport import DataPlane
    from tpu_dist.dist.store import TCPStore
    model, _, _, rows = latent_model
    store = TCPStore(is_master=True)
    dp0, dp1 = DataPlane(store, 0, 2), DataPlane(store, 1, 2)
    try:
        template = serve.kv_template(model.init_slot_cache(1, 16))
        kv0, kv1 = (serve.KVTransfer(dp, template) for dp in (dp0, dp1))
        err = []

        def send():
            try:
                kv0.send(1, 7, rows, length=10, first_tok=42)
            except Exception as e:     # surfaces in the assert below
                err.append(e)
        t = threading.Thread(target=send)
        t.start()
        got = kv1.fetch(0, 7, 30.0)
        t.join(30)
        assert not err and not t.is_alive(), err
        assert got["length"] == 10 and got["first_tok"] == 42
        assert set(got["rows"]) == set(rows)
        for path in rows:
            assert set(got["rows"][path]) == {"latent"}
            np.testing.assert_array_equal(got["rows"][path]["latent"],
                                          rows[path]["latent"][..., :10])
    finally:
        dp0.close(), dp1.close()
        store.close()


def test_the_disaggregated_engine_lands_prefilled_rows_and_decodes(
        latent_model):
    """Rows prefilled elsewhere land in slot 1; the decode step over them
    gives the logits of a prefill + step in one place."""
    from tpu_dist.serve.disagg import DisaggSlotEngine
    model, params, prompt, rows = latent_model
    eng = DisaggSlotEngine(model, params, kv=None, dispatch_ch=None,
                           arrive_ch=None, num_slots=2, max_len=32, rank=0)
    eng.cache = eng._inject(eng.cache, nn.cache.pad_time(rows, 16),
                            np.int32(1))
    for path, entry in eng.cache.items():
        got = np.asarray(entry["latent"])
        np.testing.assert_array_equal(got[1, :, :16], rows[path]["latent"][0])
        assert not got[0].any()
    step = jax.jit(model.decode_step)
    there, _, _ = step(params, np.array([0, 5], np.int32),
                       np.array([0, 16], np.int32), eng.cache)
    pool = nn.cache.write_slot_rows(model.init_slot_cache(2, 32),
                                    nn.cache.pad_time(rows, 32), 1)
    here, _, _ = step(params, np.array([0, 5], np.int32),
                      np.array([0, 16], np.int32), pool)
    np.testing.assert_allclose(there[1], here[1], atol=1e-6)


def test_sharded_serving_refuses_the_latent_by_name():
    dense = _model(first_k_dense_replace=2)      # no expert layer to refuse
    with pytest.raises(NotImplementedError,
                       match=r"block0\.attn\.latent.*no head axis"):
        serve.ShardedLM(dense, 0, 2)

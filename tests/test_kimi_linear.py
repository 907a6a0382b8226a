"""Kimi Linear's hybrid block through the program, against the plain reference.

The published model (moonshotai/Kimi-Linear-48B-A3B-Instruct) is 27 layers of
width 2304 in periods of four, three Kimi Delta Attention layers (32 heads of
128, three convolutions of width 4, a float32 state whose decay is a vector of
128 a head through a low-rank pair) then one latent attention layer WITHOUT
positions (32 heads of 128 + 64 over a 512 + 64 latent, no query rank), one
leading dense layer and then 256 routed experts of 1024, 8 a token by sigmoid
scores with a selection bias, and a shared expert.  Here the same block at a
small size on the CPU, float32, seeded random weights: dim 64, KDA 4 heads of
16, latent attention 4 heads of 8 + 8 over a 16 + 8 latent, a dense layer of
96, 32 experts of width 32 with 4 a token and a shared expert; 8 layers (two
periods) against the reference, 4 (one period) where only the program is
compared with itself, to keep the compiles to seconds.  The reference is
``chipbench/reference/kimi_linear.py`` (plain ``jax.numpy``: the recurrence
token by token, attention expanded and dense, every expert computed densely
over every token), the same file the cell ``serve-kimilinear-reason`` verifies
against on the chip at the published widths.

Tolerances.  Program and reference compute the same float32 mathematics in
another order (a chunked scan in sub-blocks against a recurrence, grouped
rows against a dense masked sum, an absorbed latent against expanded keys), so
they agree to a few float32 roundings of logits of size ~1: 2e-5 (ISSUE 40).
What must not depend on the bucket or on the neighbours is the same
mathematics over the same real positions; only the shapes of the matrix
products differ: 2e-5 too, far under what a state advanced over ONE padded
position moves the logits by (checked below).
"""

import importlib.util
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn, serve
from tpu_dist.models import KimiK2LM, KimiLinearLM, Qwen3NextLM
from tpu_dist.nn.deltanet import gated_delta_chunked, gated_delta_step

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the published lists, cut after the twelfth layer: a model of fewer layers
# reads the entries it has layers for
LISTS = dict(kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11],
             full_attn_layers=[4, 8, 12], num_heads=4, head_dim=16,
             short_conv_kernel_size=4)
CFG = dict(vocab_size=211, hidden_size=64, num_hidden_layers=4,
           num_attention_heads=4, q_lora_rank=None, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
           mla_use_nope=True, intermediate_size=96, first_k_dense_replace=1,
           moe_layer_freq=1, num_experts=32, router_num_experts=32,
           expert_offset=0, num_experts_per_token=4,
           moe_intermediate_size=32, num_shared_experts=1,
           moe_renormalize=True, routed_scaling_factor=2.446,
           moe_router_activation_func="sigmoid", num_expert_group=1,
           topk_group=1, rope_theta=10000, rms_norm_eps=1e-5,
           linear_attn_config=LISTS, model_max_length=256)
CFG8 = dict(CFG, num_hidden_layers=8)
ATOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_reference", os.path.join(ROOT, "chipbench", "reference",
                                              "kimi_linear.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _fault_control():
    """The control that plants a fault in the reference on the chip; loaded
    with a fault named so that its own load of the reference is planted
    and this file's is not."""
    spec = importlib.util.spec_from_file_location(
        "kimi_linear_fault_control", os.path.join(
            ROOT, "chipbench", "tests", "fixture", "fault_control_kimilinear",
            "reference", "kimi_linear.py"))
    mod = importlib.util.module_from_spec(spec)
    with mock.patch.dict(os.environ, KIMILINEAR_FAULT="silu_gate"):
        spec.loader.exec_module(mod)
    return mod


CONTROL = _fault_control()


def _model(cfg=CFG, **over):
    lin = cfg["linear_attn_config"]
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              depth=cfg["num_hidden_layers"],
              num_heads=cfg["num_attention_heads"],
              q_lora_rank=cfg["q_lora_rank"],
              kv_lora_rank=cfg["kv_lora_rank"],
              qk_nope_head_dim=cfg["qk_nope_head_dim"],
              qk_rope_head_dim=cfg["qk_rope_head_dim"],
              v_head_dim=cfg["v_head_dim"],
              mla_use_nope=cfg["mla_use_nope"],
              dense_hidden=cfg["intermediate_size"],
              kda_layers=lin["kda_layers"],
              full_attn_layers=lin["full_attn_layers"],
              linear_num_heads=lin["num_heads"],
              linear_head_dim=lin["head_dim"],
              linear_conv_kernel=lin["short_conv_kernel_size"],
              first_k_dense_replace=cfg["first_k_dense_replace"],
              moe_layer_freq=cfg["moe_layer_freq"],
              num_experts=cfg["router_num_experts"],
              experts_held=cfg["num_experts"],
              expert_offset=cfg["expert_offset"],
              moe_top_k=cfg["num_experts_per_token"],
              moe_hidden=cfg["moe_intermediate_size"],
              num_shared_experts=cfg["num_shared_experts"],
              moe_renormalize=cfg["moe_renormalize"],
              routed_scaling_factor=cfg["routed_scaling_factor"],
              moe_router_activation_func=cfg["moe_router_activation_func"],
              num_expert_group=cfg["num_expert_group"],
              topk_group=cfg["topk_group"], rope_theta=cfg["rope_theta"],
              norm_eps=cfg["rms_norm_eps"],
              max_seq_len=cfg["model_max_length"])
    return KimiLinearLM(**dict(kw, **over))


def _perturbed(params):
    """Norm weights start at one: perturb every vector so a wrong mapping
    (the latent's norm for the output's) shows."""
    return jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim == 1 else a, params)


@pytest.fixture(scope="module")
def program():
    """One period."""
    model = _model()
    return model, _perturbed(model.init(jax.random.key(11)))


@pytest.fixture(scope="module")
def program8():
    """Two periods."""
    model = _model(CFG8)
    return model, _perturbed(model.init(jax.random.key(11)))


def _ref_logits(params, seq, cfg=CFG):
    return np.asarray(REF.forward(cfg, REF.stack_params(cfg, params),
                                  jnp.asarray(seq)[None])[0])


# -- the model -----------------------------------------------------------------

def test_layer_kinds_come_from_the_two_published_lists(program8):
    model, params = program8
    assert model.mixer_kinds == (["kda"] * 3 + ["full_attention"]) * 2
    assert model.layer_kinds == ["dense"] + ["moe"] * 7
    assert set(params["block0.attn"]) == {
        "q_weight", "k_weight", "v_weight", "q_conv_weight", "k_conv_weight",
        "v_conv_weight", "f_a_weight", "f_b_weight", "b_weight",
        "g_a_weight", "g_b_weight", "A_log", "dt_bias", "norm_weight",
        "out_weight"}
    kda = params["block0.attn"]
    assert kda["q_weight"].shape == kda["v_weight"].shape == (64, 64)
    assert kda["k_conv_weight"].shape == (64, 4)
    # the low-rank pairs go through the head's size; a decay a CHANNEL
    assert kda["f_a_weight"].shape == kda["g_a_weight"].shape == (64, 16)
    assert kda["f_b_weight"].shape == kda["g_b_weight"].shape == (16, 64)
    assert kda["A_log"].shape == (4,) and kda["dt_bias"].shape == (64,)
    assert kda["b_weight"].shape == (64, 4)
    # no query rank: one projection, heads of nope + rope
    assert set(params["block3.attn"]) == {
        "q_weight", "kv_a_weight", "kv_a_norm_weight", "kv_b_weight",
        "out_weight"}
    assert params["block3.attn"]["q_weight"].shape == (64, 4 * 16)
    assert params["block3.attn"]["kv_a_weight"].shape == (64, 24)
    assert set(params["block1.mlp"]) == {
        "router", "router_bias", "w1", "w3", "w2", "shared_w1", "shared_w3",
        "shared_w2"}
    assert params["block0.mlp.gate"]["weight"].shape == (64, 96)
    assert "bias" not in params["head"] and "pos" not in params


@pytest.mark.parametrize("lists, wrong", [
    (dict(kda_layers=[1, 2, 3], full_attn_layers=[3, 4]), [3]),
    (dict(kda_layers=[1, 2], full_attn_layers=[4]), [3]),
])
def test_a_layer_in_both_lists_or_in_neither_is_refused(lists, wrong):
    with pytest.raises(ValueError, match=rf"layers \[{wrong[0]}\]"):
        _model(**lists)


def test_the_lists_may_arrive_as_comma_separated_text():
    """How a configuration file whose harness hands scalars carries them."""
    model = _model(kda_layers="1,2,3,5,6,7", full_attn_layers="4,8")
    assert model.mixer_kinds == ["kda"] * 3 + ["full_attention"]


def test_group_limited_routing_is_refused():
    with pytest.raises(NotImplementedError, match="one group"):
        _model(num_expert_group=2)


def test_forward_logits_match_the_reference(program8):
    model, params = program8
    tokens = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 90))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for b in range(2):
        np.testing.assert_allclose(
            got[b], _ref_logits(params, tokens[b], CFG8), rtol=0, atol=ATOL)


# -- the recurrence ------------------------------------------------------------

def _recurrence_inputs(length, planted, b=2, h=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.key(length), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, length, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, length, dk)))
    v = jax.random.normal(ks[2], (b, h, length, dv))
    g = -2.0 * jax.random.uniform(ks[3], (b, h, length, dk))
    if planted:
        # channels of ONE head that forget everything at every step beside
        # channels that forget nothing: e^-80 a step, e^-5120 a chunk
        g = g.at[..., :4].set(-80.0).at[..., 4:8].set(0.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, length)))
    s0 = jax.random.normal(ks[5], (b, h, dk, dv))
    return s0, q, k, v, g, beta


def _token_by_token(s0, q, k, v, g, beta):
    def token(s, x):
        out, s = gated_delta_step(s, *x)
        return s, out

    state, out = jax.lax.scan(
        token, s0, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 2), state


@pytest.mark.parametrize("planted", [False, True],
                         ids=["drawn", "planted_minus_80_beside_0"])
@pytest.mark.parametrize("length", [1, 63, 64, 65, 200])
def test_chunked_scan_with_a_decay_a_channel_equals_the_recurrence(length,
                                                                   planted):
    """Lengths under, at and over the chunk of 64 and not a multiple of it,
    from a state that is not zero: 1e-4 relative, and neither ``inf`` nor
    ``nan`` whatever the decay."""
    args = _recurrence_inputs(length, planted)
    want_out, want_state = _token_by_token(*args)
    out, state = jax.jit(gated_delta_chunked)(*args)
    assert np.isfinite(out).all() and np.isfinite(state).all()
    for got, want in ((out, want_out), (state, want_state)):
        assert float(jnp.abs(got - want).max()) <= 1e-4 * float(
            jnp.abs(want).max())


@pytest.mark.parametrize("form", ["step", "chunked"])
def test_one_decay_on_every_channel_is_the_scalar_decay(form):
    """One recurrence: a vector whose channels are all alike gives what the
    scalar gives (Gated DeltaNet's numbers; tests/test_qwen3_next.py holds
    those to its reference)."""
    s0, q, k, v, g, beta = _recurrence_inputs(70, False)
    scalar = g[..., 0]
    vector = jnp.broadcast_to(scalar[..., None], g.shape)
    if form == "step":
        first = lambda a: a[:, :, 0]
        a = gated_delta_step(s0, *map(first, (q, k, v, scalar, beta)))
        b = gated_delta_step(s0, *map(first, (q, k, v, vector, beta)))
    else:
        a = gated_delta_chunked(s0, q, k, v, scalar, beta)
        b = gated_delta_chunked(s0, q, k, v, vector, beta)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=0, atol=2e-6)


def test_a_no_op_position_leaves_the_state_untouched():
    """``g = 0`` and ``beta = 0`` (nobody's position): the chunked scan over
    a prompt padded with such positions ends in the state after its last
    real one, bit for bit what the shorter scan gives up to summation
    order."""
    s0, q, k, v, g, beta = _recurrence_inputs(100, False)
    real = 37
    g = g.at[:, :, real:].set(0.0)
    beta = beta.at[:, :, real:].set(0.0)
    cut = lambda a: a[:, :, :real]
    _, short = gated_delta_chunked(s0, *map(cut, (q, k, v, g, beta)))
    _, padded = gated_delta_chunked(s0, q, k, v, g, beta)
    np.testing.assert_allclose(padded, short, rtol=0, atol=2e-6)


def test_the_chunk_is_whole_sub_blocks():
    from tpu_dist.nn.deltanet import _pairwise_decayed
    x = jnp.ones((1, 1, 24, 4))
    with pytest.raises(ValueError, match="whole sub-blocks"):
        _pairwise_decayed((x,), x, -x, sub=16)


def test_decay_at_initialisation_spans_memories_within_one_head(program8):
    """``exp(g)`` per token over the channels of ONE head: some forget
    within a few tokens, some still hold most after a hundred, side by
    side.  A decay taken as the mean of a head's channels is another
    model."""
    model, _ = program8
    p = model.init(jax.random.key(3))["block0.attn"]      # as initialised
    x = jax.random.normal(jax.random.key(4), (256, CFG["hidden_size"]))
    g, _ = REF.kda_gates(CFG8, p, x[None])
    decay = np.exp(np.asarray(g)[0]).mean(0)              # (H, D)
    assert np.all((decay > 0) & (decay < 1))
    spread = decay.max(-1) - decay.min(-1)
    assert spread.max() > 0.3, spread
    assert decay.max() > 0.99 and decay.min() < 0.7


# -- the latent layer's two new constructor forms ---------------------------------

def _latent(**over):
    kw = dict(embed_dim=64, num_heads=4, q_lora_rank=None, kv_lora_rank=16,
              qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
              use_nope=True)
    return nn.MultiheadLatentAttention(**dict(kw, **over))


@pytest.mark.parametrize("use_nope", [True, False], ids=["nope", "roped"])
@pytest.mark.parametrize("rank", [None, 24], ids=["no_rank", "rank24"])
def test_expanded_equals_absorbed_in_every_constructor_form(rank, use_nope):
    """A prompt through a cache (scalar index: expanded) and the same
    positions one at a time through slot steps (vector index: absorbed)."""
    layer = _latent(q_lora_rank=rank, use_nope=use_nope)
    p = layer.init(jax.random.key(1))
    names = set(p[""])
    assert ("q_weight" in names) == (rank is None)
    assert ("q_a_weight" in names) == (rank is not None)
    x = jax.random.normal(jax.random.key(2), (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        plain = layer.apply(p, x)
        pool = {"": layer.init_cache(2, 32)}
        outs = []
        for t in range(12):
            state = nn.cache.call_state(pool, jnp.full((2,), t, jnp.int32))
            out, state = layer.apply(p, x[:, t:t + 1], state=state)
            pool, _ = nn.cache.split_state(state)
            outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), plain, rtol=0,
                               atol=1e-5)


def test_without_rotation_the_layer_has_no_positions():
    """``use_nope``: a prompt's LAST output depends on the set of earlier
    positions, not their order; a roped layer's does."""
    x = jax.random.normal(jax.random.key(5), (1, 9, 64))
    swapped = x.at[:, [1, 6]].set(x[:, [6, 1]])
    for use_nope, same in ((True, True), (False, False)):
        layer = _latent(use_nope=use_nope)
        p = layer.init(jax.random.key(1))
        a, b = layer.apply(p, x)[:, -1], layer.apply(p, swapped)[:, -1]
        assert bool(jnp.allclose(a, b, atol=1e-5)) is same


def test_kimi_k2_builds_its_latent_layer_as_before():
    """The repair's other half: a rank and a rotation as before, the same
    parameters under the same names from the same keys."""
    model = KimiK2LM(97, dim=32, depth=1, num_heads=2, q_lora_rank=12,
                     kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
                     v_head_dim=8, dense_hidden=48, max_seq_len=32)
    attn = model.block0.attn
    assert attn.q_lora_rank == 12 and attn.use_nope is False
    p = model.init(jax.random.key(0))["block0.attn"]
    assert list(p) == ["q_a_weight", "q_a_norm_weight", "q_b_weight",
                       "kv_a_weight", "kv_a_norm_weight", "kv_b_weight",
                       "out_weight"]


# -- serving -------------------------------------------------------------------

def _pool(model, slots=4, max_len=256):
    return model.init_slot_cache(slots, max_len), model.init_moe_counters()


def _serve_one(model, params, prompt, n_new, slot, pool, bucket, others=None):
    """Prefill ``prompt`` (padded to ``bucket`` with a token that is not
    zero) into ``slot`` and decode ``n_new`` greedy tokens; ``others`` =
    {slot: (token, length)} keeps those slots decoding beside it (every
    other slot is FREE, length 0).  Returns the logits rows, the tokens and
    the pool."""
    padded = np.full(bucket, 5, np.int32)
    padded[:len(prompt)] = prompt
    prefill = jax.jit(model.prefill_into_slot)
    decode = jax.jit(model.decode_step)
    row, *pool = prefill(params, padded, len(prompt), slot, *pool)
    rows, toks = [np.asarray(row)], [int(np.argmax(row))]
    slots = len(jax.tree.leaves(pool[0])[0])
    tokens, lengths = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
    for s, (tok, length) in (others or {}).items():
        tokens[s], lengths[s] = tok, length
    for i in range(n_new - 1):
        tokens[slot], lengths[slot] = toks[-1], len(prompt) + i
        logits, *pool = decode(params, tokens, lengths, *pool)
        for s in (others or {}):
            tokens[s] = int(np.argmax(logits[s]))
            lengths[s] += 1
        rows.append(np.asarray(logits[slot]))
        toks.append(int(np.argmax(rows[-1])))
    return np.stack(rows), toks, pool


@pytest.mark.parametrize("bucket", [64, 128])
def test_prefill_then_decode_match_the_reference_position_by_position(
        program8, bucket):
    """Through the slot cache, free slots beside the busy one: every
    position's logits are the reference's full forward's, whatever the
    bucket."""
    model, params = program8
    prompt = np.random.default_rng(1).integers(0, CFG["vocab_size"], 45)
    with jax.default_matmul_precision("highest"):
        rows, toks, _ = _serve_one(model, params, prompt, 12, 2,
                                   _pool(model), bucket=bucket)
    full = np.concatenate([prompt, toks])
    ref = _ref_logits(params, full, CFG8)[len(prompt) - 1:-1]
    np.testing.assert_allclose(rows, ref, rtol=0, atol=ATOL)


def test_a_request_does_not_depend_on_its_bucket_or_its_neighbours(program):
    """The same request in a 64 and a 128 bucket, alone in the pool and
    between two busy slots (and a free one): the padding is a no-op of the
    recurrence and a neighbour's rows are its own."""
    model, params = program
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG["vocab_size"], 41)
    with jax.default_matmul_precision("highest"):
        base, toks, _ = _serve_one(model, params, prompt, 8, 1,
                                   _pool(model), bucket=64)
        wide, toks_wide, _ = _serve_one(model, params, prompt, 8, 1,
                                        _pool(model), bucket=128)
        pool = _pool(model)
        others = {}
        for slot, n in ((0, 30), (3, 77)):
            other = rng.integers(0, CFG["vocab_size"], n)
            rows, _, pool = _serve_one(model, params, other, 1, slot, pool,
                                       bucket=128)
            others[slot] = (int(np.argmax(rows[0])), n)
        busy, toks_busy, _ = _serve_one(model, params, prompt, 8, 1, pool,
                                        bucket=64, others=others)
    assert toks == toks_wide == toks_busy
    np.testing.assert_allclose(wide, base, rtol=0, atol=ATOL)
    np.testing.assert_allclose(busy, base, rtol=0, atol=ATOL)


def test_a_state_advanced_over_padding_or_a_tail_cut_late_would_show(program):
    """What the tolerance above is measured against: ONE more real position
    (the padding token read as the request's, in the state and in the three
    tails) moves the first logits by far more."""
    model, params = program
    prompt = np.random.default_rng(3).integers(0, CFG["vocab_size"], 41)
    padded = np.full(64, 5, np.int32)
    padded[:41] = prompt
    prefill = jax.jit(model.prefill_into_slot)
    right, *_ = prefill(params, padded, 41, 0, *_pool(model))
    wrong, *_ = prefill(params, padded, 42, 0, *_pool(model))
    assert np.abs(np.asarray(right) - np.asarray(wrong)).max() > 10 * ATOL


def test_a_reused_slot_carries_nothing_over(program):
    """A slot that held a longer request serves the next one as a fresh
    pool does: the state and the three tails are written entire at
    admission."""
    model, params = program
    rng = np.random.default_rng(4)
    long = rng.integers(0, CFG["vocab_size"], 120)
    short = rng.integers(0, CFG["vocab_size"], 19)
    with jax.default_matmul_precision("highest"):
        _, _, pool = _serve_one(model, params, long, 6, 2, _pool(model),
                                bucket=128)
        reused, toks_reused, _ = _serve_one(model, params, short, 8, 2, pool,
                                            bucket=32)
        fresh, toks_fresh, _ = _serve_one(model, params, short, 8, 2,
                                          _pool(model), bucket=32)
    assert toks_reused == toks_fresh
    np.testing.assert_array_equal(reused, fresh)


def test_free_slots_keep_their_state_through_a_decode_step(program):
    model, params = program
    cache, counters = _pool(model)
    cache = jax.tree.map(lambda a: a + 1, cache)
    lengths = np.array([0, 7, 0, 0], np.int32)
    _, after, _ = jax.jit(model.decode_step)(
        params, np.array([0, 3, 0, 0], np.int32), lengths, cache, counters)
    seen = set()
    for path, entry in after.items():
        for name in entry:
            if not nn.cache.is_timed(name):
                seen.add(name)
                free = np.array([0, 2, 3])
                np.testing.assert_array_equal(
                    np.asarray(after[path][name])[free],
                    np.asarray(cache[path][name])[free])
                assert not np.array_equal(np.asarray(after[path][name])[1],
                                          np.asarray(cache[path][name])[1])
    assert seen == {"state", "conv_q", "conv_k", "conv_v"}


def test_generate_is_the_slot_engines_tokens(program):
    """``generate()`` too: the offline loop runs on the same two methods."""
    model, params = program
    prompt = np.random.default_rng(6).integers(0, CFG["vocab_size"], (1, 23))
    with jax.default_matmul_precision("highest"):
        out = np.asarray(model.generate(params, jnp.asarray(prompt), 6))
        rows, toks, _ = _serve_one(model, params, prompt[0], 6, 0,
                                   _pool(model, slots=2), bucket=32)
    assert out[0, 23:].tolist() == toks


# -- the engine: a slot of whole state beside a headless latent -------------------

def test_slot_engine_serves_the_reference_tokens_and_counts_by_hand(program):
    """Through ``SlotEngine`` (bucketed prefill, the launch-ahead halves,
    two requests side by side): every served token is the reference's
    largest logit at its position; and ``stats()["state"]``,
    ``["decode_need"]`` and ``["prefill_attn"]`` against hand counts for a
    slot that holds 3 layers of state and tails and 1 layer of latent."""
    model, params = program
    engine = serve.SlotEngine(model, params, num_slots=3, max_len=128,
                              min_bucket=32)
    # a slot: 3 KDA layers x (4 heads x 16 x 16 float32 + 3 tails of 3 x 64
    # float32), whatever the context; 24 float32 a position in 1 layer
    state_bytes = 3 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert nn.cache.slot_bytes(engine.cache) == (state_bytes, 24 * 4)
    need = engine._need
    assert need["attend_flops"] == 2 * 4 * (24 + 16)       # the one latent layer
    assert need["state_flops"] == 3 * 7 * 4 * 16 * 16      # the three KDA layers
    size = lambda tree: sum(int(a.size) for a in jax.tree.leaves(tree))
    experts = sum(size({k: params[f"block{i}.mlp"][k]
                        for k in ("w1", "w2", "w3")}) for i in (1, 2, 3))
    fixed = size(params) - 211 * 64 - experts
    assert need["fixed_params"] == fixed
    assert model.slot_decode_kernel(engine.cache) is False   # a CPU run
    got = {}
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, CFG["vocab_size"], n) for n in (21, 50)]
    for i, prompt in enumerate(prompts):
        got[i] = []
        engine.launch_admit(serve.Request(
            prompt, 9, on_token=lambda _, tok, i=i: got[i].append(tok)))
        engine.settle()
    while not engine.idle():
        if engine.launch_step():
            engine.settle()
        else:
            engine.collect_all()
    for i, prompt in enumerate(prompts):
        assert len(got[i]) == 9
        ref = _ref_logits(params, np.concatenate([prompt, got[i]]))
        ref = ref[len(prompt) - 1:-1]
        margin = ref.max(-1) - ref[np.arange(9), got[i]]
        assert margin.max() <= ATOL, margin
    st = engine.stats()
    # 8 decode steps over two busy slots, 21 + i and 50 + i resident
    positions = sum((21 + i + 1) + (50 + i + 1) for i in range(8))
    assert st["state"] == {"state_bytes": 2 * state_bytes * 16,
                           "kv_bytes": 24 * 4 * positions,
                           "steps": 8, "kernel_steps": 0}      # a CPU run
    dn, moe = st["decode_need"], st["moe"]["by_phase"]["decode"]
    assert (dn["steps"], dn["rows"], dn["positions"]) == (8, 16, positions)
    assert dn["cache_bytes"] == (st["state"]["state_bytes"]
                                 + st["state"]["kv_bytes"])
    assert dn["weight_bytes"] == (8 * 4 * fixed
                                  + moe["experts_hit"] * 4 * 3 * 64 * 32)
    assert dn["flops"] == (2 * (fixed * 16 + moe["held_rows"] * 3 * 64 * 32)
                           + 2 * 4 * 40 * positions
                           + 3 * 7 * 4 * 16 * 16 * 16)
    # two prefills (32 and 64 buckets) of one latent layer, dense: the
    # whole square a head (a request's row of its program: the absent
    # prompts beside it are prefill_absent_rows', ISSUE 48)
    assert st["prefill_attn"] == {
        "prefills": 2, "kernel_prefills": 0,
        "pairs_needed": 4 * (21 * 22 // 2 + 50 * 51 // 2),
        "pairs_executed": 4 * (32 * 32 + 64 * 64)}
    whole = st["moe"]
    assert whole["absent_rows"] == 0 and whole["held_rows"] == whole["rows"]


def _pallas_call_names(jaxpr):
    """The ``name`` of every ``pallas_call`` of a jaxpr, sub-jaxprs too."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_pallas_call_names(sub))
    return names


def test_a_prefill_with_a_decay_a_channel_never_takes_the_scan_kernel():
    """ISSUE 42: tpu_dist.ops.delta_scan computes the scalar-decay form;
    Kimi Delta Attention's pairwise decays do not factor out of a chunk's
    products, so with heads the kernel WOULD take (128 x 128, float32) and
    the kernels forced, the model answers false, its prefill program holds
    no ``delta_scan`` call (its decode step does hold ``delta_step``), the
    engine counts no kernel prefill, and the tokens are the ``jax.numpy``
    form's."""
    model = KimiLinearLM(
        97, dim=64, depth=2, num_heads=4, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        dense_hidden=96, kda_layers=[1], full_attn_layers=[2],
        linear_num_heads=2, linear_head_dim=128, num_experts=8,
        moe_top_k=2, moe_hidden=32, max_seq_len=64)
    params = model.init(jax.random.key(0))
    pool, counters = model.init_slot_cache(2, 64), model.init_moe_counters()
    with nn.attention_impl("flash"):
        assert model.prefill_scan_kernel(pool, 32) is False
        assert model.slot_state_kernel(pool) is True
        prefill = jax.make_jaxpr(model.prefill_into_slot)(
            params, np.zeros(32, np.int32), 20, 0, pool, counters)
        decode = jax.make_jaxpr(model.decode_step)(
            params, np.zeros(2, np.int32), np.zeros(2, np.int32), pool,
            counters)
    assert "delta_scan" not in _pallas_call_names(prefill.jaxpr)
    assert "delta_step" in _pallas_call_names(decode.jaxpr)

    def served(impl):
        got = []
        with nn.attention_impl(impl):
            engine = serve.SlotEngine(model, params, num_slots=2, max_len=64,
                                      min_bucket=16)
            engine.admit(serve.Request(
                np.arange(1, 21), 4, on_token=lambda _, tok: got.append(tok)))
            while not engine.idle():
                engine.step()
        return got, engine.stats()["prefill_scan"]

    got, scan = served("flash")
    want, _ = served("dense")
    assert got == want and len(got) == 4
    assert scan == {"prefills": 1, "kernel_prefills": 0}


def test_gated_deltanet_gets_the_state_operations_too():
    model = Qwen3NextLM(97, dim=32, depth=1, num_heads=2, num_kv_heads=1,
                        head_dim=16, linear_key_heads=1, linear_value_heads=2,
                        linear_key_dim=8, linear_value_dim=4, num_experts=4,
                        moe_top_k=2, moe_hidden=16, shared_hidden=16,
                        max_seq_len=32)
    assert model.block0.attn.state_flops_per_row == 7 * 2 * 8 * 4
    assert model.block0.attn.attend_flops_per_position == 0
    engine = serve.SlotEngine(model, model.init(jax.random.key(0)),
                              num_slots=2, max_len=32)
    assert engine._need["state_flops"] == 7 * 2 * 8 * 4
    assert engine._need["attend_flops"] == 0


def _rows(model, length=8):
    return jax.tree.map(np.asarray, model.init_slot_cache(1, length))


def _refusals(model):
    """The four movers, each asked to move this model's cache."""
    from tpu_dist.serve.disagg import DisaggSlotEngine
    return {
        "prefix": lambda: serve.PrefixCache(block_tokens=4).insert(
            np.arange(8), _rows(model), 8),
        "kvtransfer": lambda: serve.KVTransfer(
            None, serve.kv_template(model.init_slot_cache(1, 16))),
        "disagg": lambda: DisaggSlotEngine(
            model, model.init(jax.random.key(0)), kv=None, dispatch_ch=None,
            arrive_ch=None, num_slots=2, max_len=32, rank=0),
        "sharded": lambda: serve.ShardedLM(model, 0, 2),
    }


@pytest.mark.parametrize("mover", ["prefix", "kvtransfer", "disagg",
                                   "sharded"])
def test_the_movers_refuse_the_model_as_they_refuse_qwen3_next(mover):
    """By the leaf's name, with the message Qwen3-Next gets: a state leaf
    has no time axis, whatever else the slot holds."""
    said = {}
    hybrid = Qwen3NextLM(97, dim=32, depth=1, num_heads=2, num_kv_heads=1,
                         head_dim=16, linear_key_heads=1,
                         linear_value_heads=2, linear_key_dim=8,
                         linear_value_dim=4, num_experts=4, moe_top_k=2,
                         moe_hidden=16, shared_hidden=16, max_seq_len=32)
    for name, model in (("kimi", _model()), ("qwen", hybrid)):
        with pytest.raises(
                NotImplementedError,
                match=r"block0\.attn\.(state|conv\w*).*no time axis") as e:
            _refusals(model)[mover]()
        said[name] = str(e.value)
    # the same sentence but for the leaf named and the count of such leaves
    blank = lambda s: re.sub(r"'block0\.attn\.\w+'|\d+ such", "_", s)
    assert blank(said["kimi"]) == blank(said["qwen"])


# -- the share -----------------------------------------------------------------

def test_the_shares_of_all_chips_add_up_to_the_whole_layer(program):
    """The share tied to the model: eight chips hold 4 of the 32 experts
    each.  The routed parts of the eight shares, plus the shared expert
    counted once, are the uncut reference's whole expert layer."""
    model, params = program
    whole = params["block1.mlp"]
    x = jax.random.normal(jax.random.key(9), (50, CFG["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        shared = REF.moe_shared(whole, x)
        want = REF.moe_routed(CFG, whole, x) + shared
        total = jnp.zeros_like(x)
        for chip in range(8):
            layer = nn.MoELayer(
                CFG["hidden_size"], 32, hidden=32, top_k=4,
                normalize_gates=True, dispatch="dropless", gated=True,
                shared_hidden=32, shared_gate=False, scoring="sigmoid",
                selection_bias=True, routed_scale=2.446, experts_held=4,
                expert_offset=4 * chip)
            held = slice(4 * chip, 4 * chip + 4)
            share = dict(whole, **{k: whole[k][held]
                                   for k in ("w1", "w3", "w2")})
            out = layer.apply({"": share}, x)
            # the program's share equals the reference GIVEN the same share
            cfg = dict(CFG, num_experts=4, expert_offset=4 * chip)
            np.testing.assert_allclose(
                out, REF.moe_routed(cfg, share, x) + shared, atol=ATOL)
            total = total + (out - shared)
    np.testing.assert_allclose(total + shared, want, rtol=0, atol=ATOL)


def test_a_share_of_the_model_serves_the_reference_given_the_same_share():
    """The configuration's cut at a small size: 4 of 32 experts held from
    expert 8, the router over all 32."""
    cfg = dict(CFG, num_experts=4, expert_offset=8)
    model = _model(cfg)
    params = _perturbed(model.init(jax.random.key(5)))
    assert params["block1.mlp"]["w1"].shape[0] == 4
    assert params["block1.mlp"]["router"].shape == (64, 32)
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 50)
    with jax.default_matmul_precision("highest"):
        rows, toks, pool = _serve_one(model, params, prompt, 4, 0,
                                      _pool(model, slots=2), bucket=64)
    ref = _ref_logits(params, np.concatenate([prompt, toks]), cfg)
    np.testing.assert_allclose(rows, ref[len(prompt) - 1:-1], rtol=0,
                               atol=ATOL)
    c = jax.tree.map(np.asarray, pool[1])["block1.mlp"]
    assert c["held_rows"] == c["rows"][8:12].sum() < c["rows"].sum()


def test_a_width_that_is_not_whole_tiles_pads_no_weight():
    """The model's width, 2,304 = 4.5 tiles of 512: the grouped matmul of
    the experts' down-projection takes the widest tile that divides it
    (384) where padding the width up to 2,560 copied the whole weight stack
    at every call (PERF.md, PR 40).  Here 1,152 = 2.25 tiles of 512."""
    from tpu_dist.ops.gmm import _dividing_tile, gmm
    assert _dividing_tile(2304, 512) == 384 and _dividing_tile(1152, 512) == 384
    assert [_dividing_tile(w, 512) for w in (512, 1024, 2048, 3584, 7168)] \
        == [512] * 5
    assert _dividing_tile(128, 16) == 16 and _dividing_tile(256, 256) == 256
    e, d, h, b = 3, 32, 1152, 8
    ks = jax.random.split(jax.random.key(0), 2)
    x = jax.random.normal(ks[0], (4 * b, d))
    w = jax.random.normal(ks[1], (e, d, h))
    groups = jnp.array([0, 1, 1, 2], jnp.int32)
    call = lambda x, w: gmm(x, w, groups, jnp.int32(4), block_rows=b)
    want = jnp.concatenate([x[i * b:(i + 1) * b] @ w[g]
                            for i, g in enumerate([0, 1, 1, 2])])
    np.testing.assert_allclose(call(x, w), want, rtol=1e-5, atol=1e-4)
    grown = [eqn for eqn in jax.make_jaxpr(call)(x, w).eqns
             if eqn.primitive.name == "pad"
             and eqn.outvars[0].aval.shape[-1] > h]
    assert not grown, grown


# -- what the chip's faults are on the CPU -----------------------------------------

@pytest.mark.parametrize("fault", CONTROL.FAULTS)
def test_a_fault_in_the_mathematics_shows_in_the_logits(program8, fault,
                                                        monkeypatch):
    """The three the cell's ``logit_tol`` is shown to catch on the chip
    (chipbench/tests/fixture/fault_control_kimilinear), here at the small
    size: each moves the reference's logits far beyond the tolerance."""
    model, params = program8
    tokens = np.random.default_rng(7).integers(0, CFG["vocab_size"], 80)
    right = _ref_logits(params, tokens, CFG8)
    monkeypatch.setattr(REF, *CONTROL.faulty(REF, fault))
    wrong = _ref_logits(params, tokens, CFG8)
    assert np.abs(wrong - right).max() > 100 * ATOL

"""LFM2-MoE's layers through the program, against the plain reference.

The published model (LiquidAI/LFM2-24B-A2B) is 40 layers of width 2048 whose
token mixer is, by the published list ``layer_types``, a GATED SHORT
CONVOLUTION (30 layers: ``[B | C | u] = x W_in``, ``B * u`` through a causal
depthwise convolution of 3 taps with no activation, times ``C``, ``W_out``)
or a grouped-query attention with a norm over every query and key head (10
layers: 32 query heads over 8 K/V heads of 64, rope at theta 1e6); the first
two layers carry a dense SwiGLU of 11,776, the other 38 an expert layer of 64
SwiGLU experts of 1,536, four a token, scored by a sigmoid, chosen with a
per-expert bias that stays out of the weights, normalised, no shared expert.
Here the same layers at a small size on the CPU, float32, seeded random
weights, with every ratio kept: width 64, 8 query heads over 2 K/V heads of 8
(4 a K/V head), 16 experts of 32, four a token, a dense MLP of 96, and the
first four entries of the published list (``conv, conv, full_attention,
conv``: both dense layers, and an expert layer under each kind of mixer).
The reference is
``chipbench/reference/lfm2_moe.py`` (plain ``jax.numpy``: the convolution
position by position, attention dense, every expert over every token), the
same file the cell ``serve-lfm2moe-reason`` verifies against on the chip at
the published widths.

Tolerance.  Program and reference compute the same float32 mathematics in
another order (taps summed over a window against a carried tail, grouped
query rows against repeated keys, a grouped matmul against a dense one under
a mask), so they agree to a few float32 roundings of logits of size ~1: 2e-5
(ISSUE 47).
"""

import importlib.util
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn, serve
from tpu_dist.models import Lfm2MoeLM
from tpu_dist.models.lfm2_moe import EMBEDDING_STD, EXPERT_DEVIATION
from tpu_dist.nn.shortconv import causal_conv

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the published list, all 40 entries
PUBLISHED = ["full_attention" if i % 4 == 2 else "conv" for i in range(40)]
CFG = dict(
    vocab_size=211, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=2, layer_types=PUBLISHED[:4],
    intermediate_size=96, moe_intermediate_size=32, num_dense_layers=2,
    num_experts=16, num_experts_per_tok=4, conv_L_cache=3, conv_bias=False,
    norm_eps=1e-5, norm_topk_prob=True, routed_scaling_factor=1,
    use_expert_bias=True,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    max_position_embeddings=256)
ATOL = 2e-5


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(name,
                                                  os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("lfm2_moe_reference", "chipbench", "reference", "lfm2_moe.py")
# loaded with a fault named, so that its own load of the reference is planted
# and this file's is not
with mock.patch.dict(os.environ, LFM2MOE_FAULT="conv_silu"):
    CONTROL = _load("lfm2_moe_fault_control", "chipbench", "tests", "fixture",
                    "fault_control_lfm2moe", "reference", "lfm2_moe.py")


def _model(cfg=CFG, **over):
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              depth=cfg["num_hidden_layers"],
              num_heads=cfg["num_attention_heads"],
              num_kv_heads=cfg["num_key_value_heads"],
              layer_types=",".join(cfg["layer_types"]),
              dense_hidden=cfg["intermediate_size"],
              num_dense_layers=cfg["num_dense_layers"],
              conv_kernel=cfg["conv_L_cache"], conv_bias=cfg["conv_bias"],
              num_experts=cfg["num_experts"],
              moe_top_k=cfg["num_experts_per_tok"],
              moe_hidden=cfg["moe_intermediate_size"],
              moe_normalize_gates=cfg["norm_topk_prob"],
              routed_scaling_factor=cfg["routed_scaling_factor"],
              use_expert_bias=cfg["use_expert_bias"],
              rope_theta=cfg["rope_parameters"]["rope_theta"],
              norm_eps=cfg["norm_eps"],
              max_seq_len=cfg["max_position_embeddings"])
    return Lfm2MoeLM(**dict(kw, **over))


def _perturbed(params):
    """Norm weights start at one and the router's bias within 0.01 of zero:
    perturb every vector so that a wrong mapping, a norm on the wrong side
    of the rotation and a bias in the weights show."""
    return jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim == 1 else a, params)


@pytest.fixture(scope="module")
def program():
    model = _model()
    return model, _perturbed(model.init(jax.random.key(11)))


@pytest.fixture(scope="module")
def methods(program):
    """The pool programs' two methods, jitted once for the file (a jit of a
    bound method is a new function, and a new trace, at every mention)."""
    model, _ = program
    return jax.jit(model.prefill_into_slot), jax.jit(model.decode_step)


def _ref_logits(params, seq, cfg=CFG):
    return np.asarray(REF.forward(cfg, REF.stack_params(cfg, params),
                                  jnp.asarray(seq)[None])[0])


#: one sequence, read once by the reference: causal, so a request that is a
#: prefix of it (teacher-forced past the prompt) has these logits
SEQ = np.random.default_rng(0).integers(0, CFG["vocab_size"], 40)


@pytest.fixture(scope="module")
def seq_logits(program):
    return _ref_logits(program[1], SEQ)


# -- the model -----------------------------------------------------------------

@pytest.mark.parametrize("depth", [10, 40])
def test_layer_kinds_and_dense_count_of_the_published_list(depth):
    """At the published widths (nothing is drawn: a model is its modules):
    the first stage of ten and the whole forty."""
    model = Lfm2MoeLM(65536, dim=2048, depth=depth, num_heads=32,
                      num_kv_heads=8, layer_types=",".join(PUBLISHED),
                      dense_hidden=11776)
    assert model.mixer_kinds == PUBLISHED[:depth]
    attention = [i for i, k in enumerate(model.mixer_kinds)
                 if k == "full_attention"]
    assert attention == list(range(2, depth, 4))
    assert model.mixer_kinds.count("conv") == {10: 8, 40: 30}[depth]
    assert model.layer_kinds == ["dense"] * 2 + ["moe"] * (depth - 2)
    for i, (mix, kind) in enumerate(zip(model.mixer_kinds,
                                        model.layer_kinds)):
        block = getattr(model, f"block{i}")
        assert isinstance(block.attn, nn.GatedShortConv if mix == "conv"
                          else nn.MultiheadSelfAttention)
        assert isinstance(block.mlp, nn.GatedMLP if kind == "dense"
                          else nn.MoELayer)
    attn, moe = model.block2.attn, model.block2.mlp
    assert (attn.num_heads, attn.num_kv_heads, attn.head_dim) == (32, 8, 64)
    assert attn.qk_norm == "head"
    assert (moe.num_experts, moe.experts_held, moe.top_k, moe.hidden,
            moe.shared_hidden, moe.scoring, moe.selection_bias) == (
        64, 64, 4, 1536, 0, "sigmoid", True)
    assert model.block0.attn.short_conv_params == 16_783_360


def test_parameters_by_name_and_shape(program):
    model, params = program
    assert [m._path for m in model._mixers()] == [
        f"block{i}.attn" for i in range(4)]
    conv = params["block0.attn"]
    assert {k: v.shape for k, v in conv.items()} == {
        "in_weight": (64, 192), "conv_weight": (64, 3),
        "out_weight": (64, 64)}
    attn = params["block2.attn"]
    assert {k: v.shape for k, v in attn.items()} == {
        "qkv_weight": (64, 64 + 2 * 16), "out_weight": (64, 64),
        "q_norm_weight": (8,), "k_norm_weight": (8,)}
    assert params["block1.mlp.gate"]["weight"].shape == (64, 96)
    assert "block1.mlp" not in params and "block2.mlp.gate" not in params
    assert {k: v.shape for k, v in params["block2.mlp"].items()} == {
        "router": (64, 16), "router_bias": (16,), "w1": (16, 64, 32),
        "w3": (16, 64, 32), "w2": (16, 32, 64)}
    assert "bias" not in params["head"] and "pos" not in params
    # a head norm's weight is held zero-centred: it starts at zero and the
    # reference reads 1 + the leaf
    fresh = _model().init(jax.random.key(0))
    np.testing.assert_array_equal(fresh["block2.attn"]["q_norm_weight"], 0.0)


def test_the_list_may_arrive_as_text_and_a_wrong_one_is_refused():
    assert _model(layer_types=CFG["layer_types"]).mixer_kinds == \
        _model().mixer_kinds == PUBLISHED[:4]
    assert _model(layer_types=PUBLISHED).mixer_kinds == PUBLISHED[:4]
    with pytest.raises(ValueError, match=r"\['sliding_attention'\]"):
        _model(layer_types="conv,sliding_attention,conv,conv")
    with pytest.raises(ValueError, match="names 3 layers, the model has 4"):
        _model(layer_types="conv,conv,full_attention")
    with pytest.raises(NotImplementedError, match="conv_bias"):
        _model(conv_bias=True)


def test_the_seeded_experts_stand_around_a_common_one():
    """``init``: a layer's experts differ by ``EXPERT_DEVIATION`` of a draw
    at the dense layers' scale and the embedding stands at
    ``EMBEDDING_STD``; the router, its bias and the other layers are the
    parent class's draws."""
    model = _model()
    drawn = model.init(jax.random.key(3))
    plain = super(Lfm2MoeLM, model).init(jax.random.key(3))
    for name, fan_in in (("w1", 64), ("w3", 64), ("w2", 32)):
        w = np.asarray(drawn["block3.mlp"][name])
        common = w.mean(0)
        bound = fan_in ** -0.5
        assert np.abs(common).max() <= bound * 1.1
        assert common.std() == pytest.approx(bound / 3 ** 0.5, rel=0.1)
        own = (w - common).std()
        assert own == pytest.approx(EXPERT_DEVIATION * bound / 3 ** 0.5,
                                    rel=0.1)
    for path, name in (("block3.mlp", "router"), ("block3.mlp", "router_bias"),
                       ("block0.attn", "in_weight"),
                       ("block1.mlp.up", "weight"), ("head", "weight")):
        np.testing.assert_array_equal(drawn[path][name], plain[path][name])
    np.testing.assert_allclose(drawn["tok"]["weight"],
                               EMBEDDING_STD * plain["tok"]["weight"])
    assert float(jnp.abs(drawn["block3.mlp"]["router_bias"]).max()) <= 0.01


def test_forward_logits_match_the_reference(program):
    model, params = program
    tokens = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 70))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logits(params, tokens[b]),
                                   rtol=0, atol=ATOL)


def test_the_new_argument_defaults_to_what_was_there():
    """The helper's activation: at its default the three older callers
    compute what they did, and without one the taps' sum as it is."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 5, 4)),
                    jnp.float32)
    tail = jnp.zeros((2, 2, 4))
    w = jnp.asarray(np.random.default_rng(2).standard_normal((4, 3)),
                    jnp.float32)
    valid = jnp.ones((2, 5), bool)
    plain, _ = causal_conv(x, tail, w, valid, activation=None)
    silu, _ = causal_conv(x, tail, w, valid)
    np.testing.assert_allclose(silu, jax.nn.silu(plain), rtol=1e-6)
    padded = np.concatenate([np.zeros((2, 2, 4)), x], 1)
    want = sum(padded[:, j:j + 5] * np.asarray(w)[:, j] for j in range(3))
    np.testing.assert_allclose(plain, want, rtol=1e-5, atol=1e-6)


# -- the convolution through the cache -----------------------------------------

def _pool(model, slots=4, max_len=256):
    return model.init_slot_cache(slots, max_len)


def _bucket(n, step=8):
    return -(-n // step) * step


@pytest.mark.parametrize("length", range(1, 2 * 8 + 3))
def test_the_convolution_through_the_cache_at_every_prompt_length(
        program, methods, seq_logits, length):
    """Every prompt length from 1 past two buckets (of 8 here), padded with
    a token that is not zero, into slot 2 of a pool whose slot 1 is busy and
    whose others are free; then two decode steps.  After a prompt of length
    1 or 2 the tail is part zeros.  Every row is the reference's at its
    position."""
    model, params = program
    bucket = _bucket(length)
    padded = np.full(bucket, 5, np.int32)
    padded[:length] = SEQ[:length]
    prefill, decode = methods
    with jax.default_matmul_precision("highest"):
        other = np.full(8, 7, np.int32)
        _, pool, _ = prefill(params, other, 3, 1, _pool(model))
        row, pool, _ = prefill(params, padded, length, 2, pool)
        rows = [np.asarray(row)]
        tokens, lengths = np.zeros(4, np.int32), np.zeros(4, np.int32)
        tokens[1], lengths[1] = 9, 3
        for i in range(2):
            tokens[2], lengths[2] = SEQ[length + i], length + i
            logits, pool, _ = decode(params, tokens, lengths, pool)
            rows.append(np.asarray(logits[2]))
            lengths[1] += 1
    np.testing.assert_allclose(np.stack(rows),
                               seq_logits[length - 1:length + 2],
                               rtol=0, atol=ATOL)
    # the tail a slot holds after a call is the last two GATED inputs of
    # its request, oldest first, whatever the bucket
    tail = np.asarray(pool["block0.attn"]["conv"])[2].reshape(2, 64)
    assert np.abs(tail[1]).max() > 0
    assert (np.abs(tail[0]).max() > 0) == (length + 2 >= 2)


def test_the_mixer_alone_against_numpy_position_by_position():
    """``nn.GatedShortConv`` by itself: a plain forward, and the same
    sequence fed through its cache entry in calls of uneven lengths with
    padding inside each, against a loop over positions."""
    layer = nn.GatedShortConv(8, conv_kernel=3)
    params = layer.init(jax.random.key(2))
    p = {k: np.asarray(v, np.float64) for k, v in params[""].items()}
    x = np.random.default_rng(3).standard_normal((1, 11, 8))
    proj = x[0] @ p["in_weight"]
    s = proj[:, :8] * proj[:, 16:]
    want = np.zeros((11, 8))
    for t in range(11):
        c = sum(p["conv_weight"][:, j] * s[t - 2 + j]
                for j in range(3) if t - 2 + j >= 0)
        want[t] = (proj[t, 8:16] * c) @ p["out_weight"]
    got = layer.apply(params, jnp.asarray(x, jnp.float32))
    np.testing.assert_allclose(got[0], want, rtol=0, atol=1e-5)
    entry, outs, at = layer.init_cache(1), [], 0
    assert {k: v.shape for k, v in entry.items()} == {"conv": (1, 16)}
    for real, padded in ((1, 4), (1, 1), (4, 6), (5, 8)):
        chunk = np.full((1, padded, 8), 3.0, np.float32)
        chunk[0, :real] = x[0, at:at + real]
        valid = (np.arange(padded) < real)[None]
        out, state = layer.apply(params, jnp.asarray(chunk), state={
            "": dict(entry, index=jnp.int32(at), valid=jnp.asarray(valid))})
        entry = {"conv": state[""]["conv"]}
        assert int(state[""]["index"]) == at + padded
        outs.append(np.asarray(out[0, :real]))
        at += real
    np.testing.assert_allclose(np.concatenate(outs), want, rtol=0, atol=1e-5)


def test_a_tail_advanced_over_padding_would_show(program, methods):
    """What the tolerance above is measured against: ONE more real position
    (the padding token read as the request's) moves the logits of the
    decode step that follows by far more."""
    model, params = program
    padded = np.full(16, 5, np.int32)
    padded[:9] = SEQ[:9]
    prefill, decode = methods
    rows = []
    for real in (9, 10):
        _, pool, _ = prefill(params, padded, real, 0, _pool(model, slots=1))
        logits, _, _ = decode(params, SEQ[9:10], np.array([9], np.int32),
                              pool)
        rows.append(np.asarray(logits[0]))
    assert np.abs(rows[0] - rows[1]).max() > 100 * ATOL


# -- serving -------------------------------------------------------------------

def _serve_one(methods, params, prompt, n_new, slot, pool, bucket,
               others=None):
    """Prefill ``prompt`` (padded to ``bucket`` with a token that is not
    zero) into ``slot`` and decode ``n_new`` greedy tokens; ``others`` =
    {slot: (token, length)} keeps those slots decoding beside it (every
    other slot is FREE, length 0).  Returns the logits rows, the tokens and
    the pool."""
    padded = np.full(bucket, 5, np.int32)
    padded[:len(prompt)] = prompt
    prefill, decode = methods
    row, pool, _ = prefill(params, padded, len(prompt), slot, pool)
    rows, toks = [np.asarray(row)], [int(np.argmax(row))]
    slots = len(jax.tree.leaves(pool)[0])
    tokens, lengths = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
    for s, (tok, length) in (others or {}).items():
        tokens[s], lengths[s] = tok, length
    for i in range(n_new - 1):
        tokens[slot], lengths[slot] = toks[-1], len(prompt) + i
        logits, pool, _ = decode(params, tokens, lengths, pool)
        for s in (others or {}):
            tokens[s] = int(np.argmax(logits[s]))
            lengths[s] += 1
        rows.append(np.asarray(logits[slot]))
        toks.append(int(np.argmax(rows[-1])))
    return np.stack(rows), toks, pool


def test_a_slot_holds_columns_in_one_layer_and_a_tail_in_three(program):
    model, _ = program
    pool = model.init_slot_cache(4, 256, jnp.bfloat16)
    assert list(pool) == [f"block{i}.attn" for i in range(4)]
    for i, kind in enumerate(PUBLISHED[:4]):
        shapes = {name: (leaf.shape, leaf.dtype)
                  for name, leaf in pool[f"block{i}.attn"].items()}
        assert shapes == ({"conv": ((4, 2 * 64), jnp.bfloat16)}
                          if kind == "conv" else
                          {"k": ((4, 2, 8, 256), jnp.bfloat16),
                           "v": ((4, 2, 8, 256), jnp.bfloat16)})
    # by hand: 3 tails of 2 x 64 numbers whatever the context; 1 attention
    # layer x 2 K/V heads x 8 x (k and v) a position
    assert nn.cache.slot_bytes(pool) == (3 * 2 * 64 * 2, 2 * 8 * 2 * 2)
    assert nn.cache.extent(pool) == (256, jnp.bfloat16)
    assert len(nn.cache.kv_entries(pool)) == 1
    assert nn.cache.state_leaves(pool) == [
        f"block{i}.attn.conv" for i in (0, 1, 3)]
    assert nn.cache.pool_leaf(pool["block0.attn"]) is None
    template = nn.cache.token_template(pool)
    assert template["block0.attn"]["conv"] == ((128,), jnp.bfloat16)
    assert template["block2.attn"]["k"] == ((2, 8), jnp.bfloat16)
    # every walker of the cache answers for an entry that is a tail alone
    assert model.slot_decode_kernel(pool) is False      # a CPU run
    assert model.slot_state_kernel(pool) is False       # no whole state
    assert model.prefill_scan_kernel(pool, 64) is False
    assert model.prefill_attention_facts(64) == {
        "kernel": False, "heads": 0, "pairs_executed": 0}
    with nn.attention_impl("flash"):
        # the grouped kernel's question reaches the attention layers alone
        assert model.slot_decode_kernel(
            model.init_slot_cache(4, 128, jnp.bfloat16)) is False  # D = 8
        wide = _model(dim=128)
        assert wide.slot_decode_kernel(
            wide.init_slot_cache(4, 128, jnp.bfloat16)) is True    # D = 16


@pytest.mark.parametrize("bucket", [64, 128])
def test_prefill_then_decode_through_a_shared_pool_match_the_reference(
        program, methods, bucket):
    """Through the slot cache, two busy slots and a free one beside the
    request's, bucket padding between them: every position's logits are the
    reference's full forward's, whatever the bucket."""
    model, params = program
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, CFG["vocab_size"], 45)
    with jax.default_matmul_precision("highest"):
        pool, others = _pool(model), {}
        for slot, n in ((0, 30), (3, 77)):
            other = rng.integers(0, CFG["vocab_size"], n)
            rows, _, pool = _serve_one(methods, params, other, 1, slot, pool,
                                       bucket=128)
            others[slot] = (int(np.argmax(rows[0])), n)
        rows, toks, _ = _serve_one(methods, params, prompt, 12, 2, pool,
                                   bucket=bucket, others=others)
    full = np.concatenate([prompt, toks])
    ref = _ref_logits(params, full)[len(prompt) - 1:-1]
    np.testing.assert_allclose(rows, ref, rtol=0, atol=ATOL)


def test_a_reused_slot_carries_nothing_over(program, methods):
    """A slot that held a longer request serves the next, shorter one as a
    fresh pool does: the tail is written entire at admission, and the
    columns past the new prompt are never read."""
    model, params = program
    rng = np.random.default_rng(4)
    long = rng.integers(0, CFG["vocab_size"], 120)
    short = rng.integers(0, CFG["vocab_size"], 1)
    with jax.default_matmul_precision("highest"):
        _, _, pool = _serve_one(methods, params, long, 6, 2, _pool(model),
                                bucket=128)
        reused, toks_reused, _ = _serve_one(methods, params, short, 8, 2, pool,
                                            bucket=32)
        fresh, toks_fresh, _ = _serve_one(methods, params, short, 8, 2,
                                          _pool(model), bucket=32)
    assert toks_reused == toks_fresh
    np.testing.assert_allclose(reused, fresh, rtol=0, atol=ATOL)


def test_free_slots_keep_their_tails_through_a_decode_step(program,
                                                           methods):
    model, params = program
    cache = jax.tree.map(lambda a: a + 1, _pool(model))
    lengths = np.array([0, 7, 0, 0], np.int32)
    _, after, _ = methods[1](
        params, np.array([0, 3, 0, 0], np.int32), lengths, cache)
    free = np.array([0, 2, 3])
    for i in (0, 1, 3):
        was, now = (np.asarray(c[f"block{i}.attn"]["conv"])
                    for c in (cache, after))
        np.testing.assert_array_equal(now[free], was[free])
        assert not np.array_equal(now[1], was[1])
        # the busy slot's tail moved on by one: its older half is the
        # newer half of what it was
        np.testing.assert_array_equal(now[1, :64], was[1, 64:])


def test_generate_serves_the_reference_tokens(program, methods):
    """``generate()`` runs on the same two methods: its tokens are the slot
    path's, and each is the reference's largest logit at its position."""
    model, params = program
    prompt = np.random.default_rng(6).integers(0, CFG["vocab_size"], (1, 23))
    with jax.default_matmul_precision("highest"):
        out = np.asarray(model.generate(params, jnp.asarray(prompt), 6))
        _, toks, _ = _serve_one(methods, params, prompt[0], 6, 0,
                                _pool(model, slots=2), bucket=32)
    assert out[0, 23:].tolist() == toks
    ref = _ref_logits(params, out[0])[22:-1]
    assert (ref.max(-1) - ref[np.arange(6), out[0, 23:]]).max() <= ATOL


# -- the engine ----------------------------------------------------------------

def test_slot_engine_serves_the_reference_tokens_and_counts_by_hand(program):
    """Through ``SlotEngine`` (bucketed prefill, the launch-ahead halves, two
    requests side by side): every served token is the reference's largest
    logit at its position; and ``stats()["state"]`` (tails alone),
    ``["conv"]``, ``["decode_need"]``, ``["decode_attn"]`` and ``["moe"]``
    against hand counts."""
    model, params = program
    engine = serve.SlotEngine(model, params, num_slots=3, max_len=128,
                              min_bucket=32)
    tail_bytes = 3 * 2 * 64 * 4
    per_pos = 2 * 8 * 2 * 4
    assert nn.cache.slot_bytes(engine.cache) == (tail_bytes, per_pos)
    need = engine._need
    assert need["attend_flops"] == 2 * 8 * 2 * 8     # one attention layer
    assert need["state_flops"] == 0
    size = lambda tree: sum(int(a.size) for a in jax.tree.leaves(tree))
    routed = 2 * 3 * 16 * 64 * 32
    fixed = size(params) - 211 * 64 - routed
    assert need["fixed_params"] == fixed
    assert need["experts"] == {f"block{i}.mlp": (3 * 64 * 32 * 4, 3 * 64 * 32)
                               for i in (2, 3)}
    conv_params = 64 * (4 * 64 + 3)
    assert engine._short_conv == {"layers": 3, "params": 3 * conv_params}
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
    assert st["state"] == {"state_bytes": 2 * tail_bytes * 16,
                           "kv_bytes": per_pos * positions,
                           "steps": 8, "kernel_steps": 0}
    assert st["conv"] == {"layers": 3, "params": 3 * conv_params,
                          "prefill": {"rows": 71, "calls": 2},
                          "decode": {"rows": 16, "calls": 8}}
    dn = st["decode_need"]
    assert (dn["steps"], dn["rows"], dn["positions"]) == (8, 16, positions)
    assert dn["cache_bytes"] == (st["state"]["state_bytes"]
                                 + st["state"]["kv_bytes"])
    assert st["decode_attn"] == {"kv_blocks_read": 16, "kv_blocks_pool": 24,
                                 "steps": 8, "block": 128, "kernel": False}
    assert st["prefill_scan"] == {"prefills": 2, "kernel_prefills": 0}
    moe = st["moe"]
    # four picks a row in each of two expert layers, every expert held
    assert moe["rows"] == moe["held_rows"] == 4 * 2 * (71 + 16)
    assert moe["absent_rows"] == 0 and moe["calls"] == 2 * (2 + 8)
    assert dn["flops"] == (2 * (fixed * 16 + 4 * 2 * 16 * 3 * 64 * 32)
                           + need["attend_flops"] * positions)
    engine.reset_stats()
    assert engine.stats()["conv"]["decode"] == {"rows": 0, "calls": 0}
    # a model without such a layer has no such entry
    from tpu_dist.models import TransformerLM
    other = TransformerLM(64, dim=16, depth=1, num_heads=2, max_seq_len=32)
    assert "conv" not in serve.SlotEngine(
        other, other.init(jax.random.key(0)), num_slots=1,
        max_len=32).stats()


def _rows(model, length=8):
    return jax.tree.map(np.asarray, model.init_slot_cache(1, length))


@pytest.mark.parametrize("mover", ["prefix", "kvtransfer", "disagg",
                                   "sharded"])
def test_the_movers_refuse_the_pool_by_the_tails_name(program, mover):
    from tpu_dist.serve.disagg import DisaggSlotEngine
    model, params = program
    moves = {
        "prefix": lambda: serve.PrefixCache(block_tokens=4).insert(
            np.arange(8), _rows(model), 8),
        "kvtransfer": lambda: serve.KVTransfer(
            None, serve.kv_template(model.init_slot_cache(1, 16))),
        "disagg": lambda: DisaggSlotEngine(
            model, params, kv=None, dispatch_ch=None, arrive_ch=None,
            num_slots=2, max_len=32, rank=0),
        "sharded": lambda: serve.ShardedLM(model, 0, 2)}
    with pytest.raises(NotImplementedError,
                       match=r"block0\.attn\.conv.*no time axis"):
        moves[mover]()


# -- what the chip's faults are on the CPU -------------------------------------

@pytest.mark.parametrize("fault", CONTROL.FAULTS)
def test_a_fault_in_the_mathematics_shows_in_the_logits(program, seq_logits,
                                                        fault, monkeypatch):
    """The nine the cell's fault control plants on the chip
    (chipbench/tests/fixture/fault_control_lfm2moe), here at the small
    size: each moves the reference's logits by over 100 tolerances."""
    _, params = program
    for name, value in CONTROL.faulty(REF, fault):
        monkeypatch.setattr(REF, name, value)
    wrong = _ref_logits(params, SEQ)
    assert np.abs(wrong - seq_logits).max() > 100 * ATOL

"""Qwen3-Next's hybrid block through the program, against the plain reference.

The published model (Qwen/Qwen3-Next-80B-A3B-Instruct) is 48 layers of width
2048 in periods of four, three Gated DeltaNet layers (16 key / 32 value heads
of 128, a convolution of width 4, a float32 state) then one gated
full-attention layer (16 query heads over 2 K/V heads of 256, per-head
QK-norm, rope on a quarter of each head), every layer with 512 routed experts
of 512, 10 a token, and a shared expert.  Here the same block at a small size
on the CPU, float32, seeded random weights: dim 64, 4 query heads over 1 K/V
head of 32 with rope on 8, DeltaNet 2 key / 4 value heads of 16, 16 experts of
width 32 with 4 a token and a shared expert of 32; 8 layers (two periods)
against the reference, 4 (one period) where only the program is compared
with itself, to keep the compiles to seconds.
The reference is ``chipbench/reference/qwen3_next.py`` (plain ``jax.numpy``:
the recurrence token by token, attention dense, every expert computed densely
over every token), the same file the cell ``serve-qwen3next-longdocs``
verifies against on the chip at the published widths.

Tolerances.  Program and reference compute the same float32 mathematics in
another order (a chunked scan against a recurrence, grouped rows against a
dense masked sum, a K/V pool against full attention), so they agree to a few
float32 roundings of logits of size ~1: 5e-5.  What must not depend on the
bucket or on the neighbours is the same mathematics over the same real
positions; only the shapes of the matrix products differ (a 64 or a 128 row
prompt, a pool row among busy or free ones), so XLA may sum in another order:
2e-5, ten times under what a state advanced over ONE padded position moves
the logits by (checked below).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn, serve
from tpu_dist.models import Qwen3NextLM
from tpu_dist.nn.deltanet import gated_delta_chunked, gated_delta_step

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dict(vocab_size=211, hidden_size=64, num_hidden_layers=4,
           num_attention_heads=4, num_key_value_heads=1, head_dim=32,
           full_attention_interval=4, partial_rotary_factor=0.25,
           rope_theta=10000000, rms_norm_eps=1e-6,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, num_experts=16, router_num_experts=16,
           expert_offset=0, num_experts_per_tok=4, moe_intermediate_size=32,
           shared_expert_intermediate_size=32, norm_topk_prob=True,
           max_position_embeddings=256)
CFG8 = dict(CFG, num_hidden_layers=8)
ATOL = 5e-5
SAME = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_reference", os.path.join(ROOT, "chipbench", "reference",
                                             "qwen3_next.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _model(cfg=CFG, **over):
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              depth=cfg["num_hidden_layers"],
              num_heads=cfg["num_attention_heads"],
              num_kv_heads=cfg["num_key_value_heads"],
              head_dim=cfg["head_dim"],
              full_attention_interval=cfg["full_attention_interval"],
              partial_rotary_factor=cfg["partial_rotary_factor"],
              rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
              linear_key_heads=cfg["linear_num_key_heads"],
              linear_value_heads=cfg["linear_num_value_heads"],
              linear_key_dim=cfg["linear_key_head_dim"],
              linear_value_dim=cfg["linear_value_head_dim"],
              linear_conv_kernel=cfg["linear_conv_kernel_dim"],
              num_experts=cfg["router_num_experts"],
              experts_held=cfg["num_experts"],
              expert_offset=cfg["expert_offset"],
              moe_top_k=cfg["num_experts_per_tok"],
              moe_hidden=cfg["moe_intermediate_size"],
              shared_hidden=cfg["shared_expert_intermediate_size"],
              moe_normalize_gates=cfg["norm_topk_prob"],
              max_seq_len=cfg["max_position_embeddings"])
    return Qwen3NextLM(**dict(kw, **over))


def _perturbed(params):
    """Norm weights start at zero or one: perturb every vector so a wrong
    mapping (``w`` for ``1 + w``, q's norm for k's) shows."""
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


def test_layer_kinds_come_from_the_one_published_scalar(program8):
    model, params = program8
    assert model.layer_kinds == ["linear_attention"] * 3 + [
        "full_attention"] + ["linear_attention"] * 3 + ["full_attention"]
    assert set(params["block0.attn"]) == {
        "qkvz_weight", "ba_weight", "conv_weight", "A_log", "dt_bias",
        "norm_weight", "out_weight"}
    assert params["block0.attn"]["qkvz_weight"].shape == (64, 32 + 32 + 64 + 64)
    assert params["block0.attn"]["conv_weight"].shape == (128, 4)
    assert set(params["block3.attn"]) == {
        "qkv_weight", "out_weight", "q_norm_weight", "k_norm_weight"}
    # [q | gate | k | v]: 4 heads of 32 twice, one K/V head of 32 twice
    assert params["block3.attn"]["qkv_weight"].shape == (64, 128 + 128 + 64)
    assert params["block3.attn"]["q_norm_weight"].shape == (32,)
    assert set(params["block0.mlp"]) == {
        "router", "w1", "w3", "w2", "shared_w1", "shared_w3", "shared_w2",
        "shared_gate"}
    assert "bias" not in params["head"] and "pos" not in params


def test_forward_logits_match_the_reference(program8):
    model, params = program8
    tokens = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 90))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for b in range(2):
        np.testing.assert_allclose(
            got[b], _ref_logits(params, tokens[b], CFG8), rtol=0, atol=ATOL)


@pytest.mark.parametrize("length", [1, 37, 64, 150])
def test_chunked_scan_equals_the_recurrence(length):
    """Lengths under, at and over the chunk of 64 and not a multiple of it,
    from a state that is not zero."""
    ks = jax.random.split(jax.random.key(length), 6)
    b, h, dk, dv = 2, 3, 16, 8
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, length, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, h, length, dk)))
    v = jax.random.normal(ks[2], (b, h, length, dv))
    g = -2.0 * jax.random.uniform(ks[3], (b, h, length))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, length)))
    s0 = jax.random.normal(ks[5], (b, h, dk, dv))

    def token(s, x):
        out, s = gated_delta_step(s, *x)
        return s, out

    s_ref, o_ref = jax.lax.scan(
        token, s0, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta)))
    out, state = gated_delta_chunked(s0, q, k, v, g, beta)
    np.testing.assert_allclose(out, jnp.moveaxis(o_ref, 0, 2), atol=2e-6)
    np.testing.assert_allclose(state, s_ref, atol=2e-6)


def test_decay_at_initialisation_spans_short_and_long_memories(program8):
    """``exp(g)`` per token over the 24 DeltaNet heads here: some forget
    within ten tokens, some still hold a third after a hundred (the
    published 288 heads reach further both ways)."""
    model, _ = program8
    params = model.init(jax.random.key(3))          # as initialised
    x = jax.random.normal(jax.random.key(4), (512, CFG["hidden_size"]))
    decays = []
    for i, kind in enumerate(model.layer_kinds):
        if kind == "linear_attention":
            p = params[f"block{i}.attn"]
            a = (x @ p["ba_weight"])[:, CFG["linear_num_value_heads"]:]
            decays.append(np.exp(-np.exp(p["A_log"]) * jax.nn.softplus(
                a + p["dt_bias"])).mean(0))
    decay = np.concatenate(decays)                  # a mean per head
    assert decay.min() < 0.9 and decay.max() > 0.98, (decay.min(),
                                                       decay.max())
    assert np.all((decay > 0) & (decay < 1))


def _pool(model, slots=4, max_len=256):
    return model.init_slot_cache(slots, max_len), model.init_moe_counters()


def _serve_one(model, params, prompt, n_new, slot, pool, bucket, others=None):
    """Prefill ``prompt`` (padded to ``bucket`` with a token that is not
    zero) into ``slot`` and decode ``n_new`` greedy tokens; ``others`` =
    {slot: (token, length)} keeps those slots decoding beside it.  Returns
    the logits rows, the tokens and the pool."""
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


def test_prefill_then_decode_match_the_reference_position_by_position(
        program8):
    model, params = program8
    prompt = np.random.default_rng(1).integers(0, CFG["vocab_size"], 45)
    with jax.default_matmul_precision("highest"):
        rows, toks, _ = _serve_one(model, params, prompt, 12, 2,
                                   _pool(model), bucket=64)
    full = np.concatenate([prompt, toks])
    ref = _ref_logits(params, full, CFG8)[len(prompt) - 1:-1]
    np.testing.assert_allclose(rows, ref, rtol=0, atol=ATOL)


def test_slot_engine_serves_the_reference_tokens(program):
    """Through ``SlotEngine`` (bucketed prefill, the launch-ahead halves,
    two requests side by side): every served token is the reference's
    largest logit at its position, to the tolerance."""
    model, params = program
    engine = serve.SlotEngine(model, params, num_slots=3, max_len=128,
                              min_bucket=32)
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
    assert st["state"]["state_bytes"] > 0 and st["state"]["kv_bytes"] > 0
    moe = st["moe"]
    assert moe["absent_rows"] == 0 and moe["held_rows"] == moe["rows"] > 0
    assert moe["computed_rows"] >= moe["held_rows"]


def test_a_request_does_not_depend_on_its_bucket_or_its_neighbours(program):
    """The same request in a 64 and a 128 bucket, alone in the pool and
    between two busy slots: the padding is a no-op of the recurrence and a
    neighbour's rows are its own."""
    model, params = program
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG["vocab_size"], 41)
    with jax.default_matmul_precision("highest"):
        base, toks, _ = _serve_one(model, params, prompt, 8, 1,
                                   _pool(model), bucket=64)
        wide, toks_wide, _ = _serve_one(model, params, prompt, 8, 1,
                                        _pool(model), bucket=128)
        # neighbours: two other requests prefilled first and kept decoding
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
    np.testing.assert_allclose(wide, base, rtol=0, atol=SAME)
    np.testing.assert_allclose(busy, base, rtol=0, atol=SAME)


# -- the prefill's scan on the Pallas kernel (ISSUE 42) -------------------------
# ``attention_impl("flash")`` makes a CPU run take tpu_dist.ops.delta_scan
# (interpreted) for a prefill and tpu_dist.ops.delta_step for a decode step;
# heads of 128 x 128 are the least either takes: a key head, two value heads.

CFG128 = dict(CFG, linear_num_key_heads=1, linear_num_value_heads=2,
              linear_key_head_dim=128, linear_value_head_dim=128)


@pytest.fixture(scope="module")
def program128():
    model = _model(CFG128)
    return model, _perturbed(model.init(jax.random.key(11)))


@pytest.mark.parametrize("bucket", [64, 128])
def test_prefill_on_the_scan_kernel_then_decode_match_the_reference(
        program128, bucket):
    """The prefill's scan through ``delta_scan`` (three bfloat16 passes a
    product, interpreted) and the decode steps through ``delta_step``:
    against the reference position by position, against the ``jax.numpy``
    form, and the same in two buckets."""
    model, params = program128
    prompt = np.random.default_rng(5).integers(0, CFG["vocab_size"], 45)
    with jax.default_matmul_precision("highest"):
        with nn.attention_impl("flash"):
            rows, toks, _ = _serve_one(model, params, prompt, 6, 2,
                                       _pool(model), bucket=bucket)
        with nn.attention_impl("dense"):
            want, toks_dense, _ = _serve_one(model, params, prompt, 6, 2,
                                             _pool(model), bucket=bucket)
    full = np.concatenate([prompt, toks])
    ref = _ref_logits(params, full, CFG128)[len(prompt) - 1:-1]
    np.testing.assert_allclose(rows, ref, rtol=0, atol=ATOL)
    assert toks == toks_dense
    np.testing.assert_allclose(rows, want, rtol=0, atol=SAME)


def _through_engine(model, params, impl, prompts, new=5):
    """``prompts`` through a ``SlotEngine`` built and run under ``impl``:
    tokens, ``stats()`` before and after ``reset_stats()``."""
    got = {i: [] for i in range(len(prompts))}
    with nn.attention_impl(impl):
        engine = serve.SlotEngine(model, params, num_slots=3, max_len=128,
                                  min_bucket=32)
        for i, prompt in enumerate(prompts):
            engine.launch_admit(serve.Request(
                prompt, new, on_token=lambda _, tok, i=i: got[i].append(tok)))
            engine.settle()
        while not engine.idle():
            if engine.launch_step():
                engine.settle()
            else:
                engine.collect_all()
    stats = engine.stats()
    engine.reset_stats()
    return got, stats, engine.stats()


def test_slot_engine_on_the_scan_kernel_serves_the_reference_tokens(
        program128):
    """Two buckets (32 and 64) through ``SlotEngine`` with the kernels
    forced: every served token is the reference's largest logit at its
    position, the tokens are the ``jax.numpy`` form's, and
    ``stats()["prefill_scan"]`` counts every prefill on the kernel."""
    model, params = program128
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, CFG["vocab_size"], n) for n in (21, 50)]
    got, scan, zeroed = _through_engine(model, params, "flash", prompts)
    want, dense, _ = _through_engine(model, params, "dense", prompts)
    scan, dense, zeroed = (a["prefill_scan"] for a in (scan, dense, zeroed))
    assert got == want
    for i, prompt in enumerate(prompts):
        ref = _ref_logits(params, np.concatenate([prompt, got[i]]), CFG128)
        ref = ref[len(prompt) - 1:-1]
        margin = ref.max(-1) - ref[np.arange(5), got[i]]
        assert margin.max() <= ATOL, margin
    assert scan == {"prefills": 2, "kernel_prefills": 2}
    assert dense == {"prefills": 2, "kernel_prefills": 0}
    assert zeroed == {"prefills": 0, "kernel_prefills": 0}


def test_the_grouped_kernel_serves_the_dense_branchs_tokens_and_logits(
        program):
    """ISSUE 45: the full-attention layer's four query heads over one K/V
    head through the grouped slot-decode kernel (``attention_impl("flash")``,
    interpreted; heads of 16 keep the recurrent layers on ``jax.numpy``
    either way), slot 1 free: the dense branch's logits step by step and its
    tokens, the free slot's K/V rows untouched, and the engine's tokens with
    ``stats()["decode_attn"]["kernel"]`` saying which branch it built."""
    model, params = program
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, CFG["vocab_size"], n) for n in (21, 50)]
    seen = {}
    for impl in ("dense", "flash"):
        with nn.attention_impl(impl), \
                jax.default_matmul_precision("highest"):
            pool = _pool(model, slots=3, max_len=128)
            assert model.slot_decode_kernel(pool[0]) is (impl == "flash")
            assert model.slot_state_kernel(pool[0]) is False
            prefill = jax.jit(lambda *a: model.prefill_into_slot(*a))
            decode = jax.jit(lambda *a: model.decode_step(*a))
            tokens, lengths = np.zeros(3, np.int32), np.zeros(3, np.int32)
            for slot, prompt in zip((0, 2), prompts):
                padded = np.full(64, 5, np.int32)
                padded[:len(prompt)] = prompt
                row, *pool = prefill(params, padded, len(prompt), slot, *pool)
                tokens[slot], lengths[slot] = int(np.argmax(row)), len(prompt)
            before, rows = jax.tree.map(np.asarray, pool[0]), []
            for _ in range(4):
                logits, *pool = decode(params, tokens, lengths, *pool)
                rows.append(np.asarray(logits)[[0, 2]])
                tokens[[0, 2]] = np.argmax(rows[-1], axis=-1)
                lengths[[0, 2]] += 1
        seen[impl] = np.stack(rows), before, jax.tree.map(np.asarray, pool[0])
    np.testing.assert_allclose(seen["flash"][0], seen["dense"][0], rtol=0,
                               atol=SAME)
    np.testing.assert_array_equal(seen["flash"][0].argmax(-1),
                                  seen["dense"][0].argmax(-1))
    _, before, after = seen["flash"]
    for name in ("k", "v"):
        np.testing.assert_array_equal(after["block3.attn"][name][1],
                                      before["block3.attn"][name][1])
        assert not np.array_equal(after["block3.attn"][name][0],
                                  before["block3.attn"][name][0])
    toks, stats, _ = _through_engine(model, params, "flash", prompts)
    want, dense, _ = _through_engine(model, params, "dense", prompts)
    assert toks == want
    assert stats["decode_attn"] == dict(dense["decode_attn"], kernel=True)
    assert dense["decode_attn"]["kernel"] is False


def test_heads_the_kernel_does_not_take_keep_the_jax_numpy_scan(program):
    """Heads of 16 x 16 (this file's usual size) fill no lane: the model
    answers false for every bucket whatever ``attention_impl`` says, and the
    engine counts no kernel prefill."""
    model, params = program
    with nn.attention_impl("flash"):
        assert model.prefill_scan_kernel(model.init_slot_cache(2, 64),
                                         64) is False
    prompts = [np.arange(1, 20)]
    _, stats, _ = _through_engine(model, params, "flash", prompts, new=2)
    assert stats["prefill_scan"] == {"prefills": 1, "kernel_prefills": 0}


def test_the_model_answers_for_its_recurrent_layers(program128):
    model, _ = program128
    cache = model.init_slot_cache(2, 64)
    with nn.attention_impl("flash"):
        assert model.prefill_scan_kernel(cache, 64) is True
        assert model.prefill_scan_kernel(cache, 1) is False
    with nn.attention_impl("dense"):
        assert model.prefill_scan_kernel(cache, 64) is False
    assert model.prefill_scan_kernel(cache, 64) is False     # a CPU backend


def test_a_state_advanced_over_padding_would_show(program):
    """What the tolerance above is measured against: ONE more real position
    (the padding token read as the request's) moves the first logits by far
    more than ``SAME``."""
    model, params = program
    prompt = np.random.default_rng(3).integers(0, CFG["vocab_size"], 41)
    padded = np.full(64, 5, np.int32)
    padded[:41] = prompt
    prefill = jax.jit(model.prefill_into_slot)
    right, *_ = prefill(params, padded, 41, 0, *_pool(model))
    wrong, *_ = prefill(params, padded, 42, 0, *_pool(model))
    assert np.abs(np.asarray(right) - np.asarray(wrong)).max() > 10 * SAME


def test_a_reused_slot_carries_nothing_over(program):
    """A slot that held a longer request serves the next one as a fresh
    pool does: the state and the tail are written entire at admission."""
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
    """A free slot's row computes garbage; its state and tail stay as they
    were (bounded, and overwritten at the next admission anyway)."""
    model, params = program
    cache, counters = _pool(model)
    cache = jax.tree.map(lambda a: a + 1, cache)
    lengths = np.array([0, 7, 0, 0], np.int32)
    _, after, _ = jax.jit(model.decode_step)(
        params, np.array([0, 3, 0, 0], np.int32), lengths, cache, counters)
    for path, entry in after.items():
        for name in entry:
            if not nn.cache.is_timed(name):
                free = np.array([0, 2, 3])
                np.testing.assert_array_equal(
                    np.asarray(after[path][name])[free],
                    np.asarray(cache[path][name])[free])
                assert not np.array_equal(np.asarray(after[path][name])[1],
                                          np.asarray(cache[path][name])[1])


def test_the_shares_of_all_chips_add_up_to_the_whole_layer(program):
    """The share tied to the model: four chips hold 4 of the 16 experts
    each.  The routed parts of the four shares, plus the shared expert
    counted once, are the uncut reference's whole expert layer."""
    model, params = program
    whole = params["block0.mlp"]
    x = jax.random.normal(jax.random.key(9), (50, CFG["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        want = REF.moe_routed(CFG, whole, x) + REF.moe_shared(whole, x)
        shared = REF.moe_shared(whole, x)
        total = jnp.zeros_like(x)
        for chip in range(4):
            layer = nn.MoELayer(
                CFG["hidden_size"], 16, hidden=32, top_k=4,
                normalize_gates=True, dispatch="dropless", gated=True,
                shared_hidden=32, experts_held=4, expert_offset=4 * chip)
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


def test_a_share_counts_held_and_absent_picks_and_computes_only_held(
        program):
    """A model that holds 4 of 16 experts from expert 8: the counters tell
    a request's picks on held experts from those on absent ones, and the
    expert matmuls run over the held ones alone (rounded up to the row
    block, nothing for the 12 absent experts)."""
    cfg = dict(CFG, num_experts=4, expert_offset=8)
    model = _model(cfg)
    params = _perturbed(model.init(jax.random.key(5)))
    prompt = np.random.default_rng(5).integers(0, cfg["vocab_size"], 50)
    with jax.default_matmul_precision("highest"):
        rows, toks, pool = _serve_one(model, params, prompt, 4, 0,
                                      _pool(model, slots=2), bucket=64)
    ref = _ref_logits(params, np.concatenate([prompt, toks]), cfg)
    np.testing.assert_allclose(rows, ref[len(prompt) - 1:-1], rtol=0,
                               atol=ATOL)
    c = jax.tree.map(np.asarray, pool[1])["block0.mlp"]
    # one 64-row prefill (50 real) and three decode steps over two slots
    # (one busy), four picks a row
    assert c["calls"] == 4
    assert c["rows"].sum() == 4 * (50 + 3) and c["pad_rows"] == 4 * (14 + 3)
    assert c["held_rows"] == c["rows"][8:12].sum() < c["rows"].sum()
    # every expert's segment is rounded up to the row block of 8 (float32)
    assert c["held_rows"] <= c["computed_rows"] <= c["held_rows"] + 4 * (
        14 + 3) + 4 * 4 * 8
    assert c["computed_rows"] < 4 * (64 + 3 * 2)      # far from every pick


def test_a_share_at_a_prefills_shape_is_combined_by_its_buffers_rows():
    """A model that holds 2 of 32 experts, 8 a token, over a 512 bucket:
    4,096 picks against a usual buffer of 768 rows (twice the expected share
    and a block of 128 an expert), so each layer's prefill is combined by
    the buffer's rows in token order (ops/moe_combine.py; the counter says
    which combine ran), the bucket's 92 padding rows among them, and serves
    the reference's logits and tokens.  The decode steps after it (16 picks)
    and the test below, which sends every pick, keep the row-gather form."""
    from tpu_dist.nn import moe
    cfg = dict(CFG, router_num_experts=32, num_experts=2, expert_offset=6,
               num_experts_per_tok=8, max_position_embeddings=512)
    model = _model(cfg)
    layer = model.block0.mlp
    assert layer._buffer_sizes(4096, layer._block_rows(
        4096, jnp.float32)) == [768, 4352]
    assert moe._combines_by_token(768, 4096)
    params = _perturbed(model.init(jax.random.key(5)))
    prompt = np.random.default_rng(6).integers(0, cfg["vocab_size"], 420)
    with jax.default_matmul_precision("highest"):
        rows, toks, pool = _serve_one(
            model, params, prompt, 3, 1, _pool(model, slots=2, max_len=512),
            bucket=512)
    ref = _ref_logits(params, np.concatenate([prompt, toks]), cfg)
    np.testing.assert_allclose(rows, ref[len(prompt) - 1:-1], rtol=0,
                               atol=ATOL)
    assert toks == [int(t) for t in ref[len(prompt) - 1:-1].argmax(-1)]
    for path, c in jax.tree.map(np.asarray, pool[1]).items():
        # one prefill by the buffer's rows (the half of the 768 that its
        # ~256 held picks fit), two decode steps over two slots by their 16
        # picks
        assert c["calls"] == 3, path
        assert c["combined_rows"] == 384 + 2 * 16, (path, c["combined_rows"])
        assert 0 < c["held_rows"] <= c["computed_rows"] <= 768 + 2 * 16


@pytest.mark.parametrize("sent", ["every_pick_held", "a_usual_share"])
def test_a_share_sent_every_pick_drops_none(sent):
    """A layer that holds 4 of 16 experts sizes its row buffer for twice
    its expected share and falls back to the buffer that holds EVERY pick
    when the rows do not fit.  A router that sends all four picks of all 64
    tokens to the four held experts takes that branch (256 rows against a
    usual buffer of 192): the output is the reference's given the same
    share and the grouped matmuls ran over every pick, none dropped."""
    layer = nn.MoELayer(
        CFG["hidden_size"], 16, hidden=32, top_k=4, normalize_gates=True,
        dispatch="dropless", gated=True, shared_hidden=32, experts_held=4,
        expert_offset=8)
    p = dict(layer.init(jax.random.key(3))[""])
    x = jax.random.normal(jax.random.key(4), (64, CFG["hidden_size"]))
    if sent == "every_pick_held":
        # one input dimension held at 4 and read by the held experts' router
        # columns alone: their logits stand ~40 over the others'
        x = x.at[:, 0].set(4.0)
        p["router"] = p["router"].at[0, 8:12].set(10.0)
    state = {"": dict(layer.init_counters(), valid=jnp.ones(64, bool))}
    with jax.default_matmul_precision("highest"):
        out, new = jax.jit(lambda p, x: layer.apply({"": p}, x, state=state))(
            p, x)
        cfg = dict(CFG, num_experts=4, expert_offset=8)
        want = REF.moe_routed(cfg, p, x) + REF.moe_shared(p, x)
    np.testing.assert_allclose(out, want, rtol=0, atol=ATOL)
    c = jax.tree.map(np.asarray, new[""])
    # 256 picks over 16 experts: row blocks of 16; the usual buffer holds
    # twice the expected share and a block more for each held expert
    usual = (-(-2 * 256 * 4 // (16 * 16)) + 4) * 16
    assert layer._block_rows(256, jnp.float32) == 16 and usual == 192
    per_expert = c["rows"][8:12]
    want_rows = int((-(-per_expert // 16) * 16).sum())
    assert c["computed_rows"] == want_rows
    assert c["combined_rows"] == 256        # every pick's row gathered
    if sent == "every_pick_held":
        assert c["held_rows"] == 256 == c["rows"].sum() and want_rows > usual
    else:
        assert 0 < c["held_rows"] < 256 // 2 and want_rows <= usual

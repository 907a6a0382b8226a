"""Falcon-H1's layer through the program, against the plain reference.

The published model (tiiuae/Falcon-H1-34B-Instruct) is 72 layers of width
5120, every one alike: ONE norm feeds a grouped-query attention (20 query
heads over 4 K/V heads of 128, rope at theta 1e11) and a Mamba-2 state-space
mixer (32 heads of 128 over a state of 256, B and C in 2 groups, one
convolution of width 4 with a bias, a gated RMSNorm over each group) SIDE BY
SIDE, their outputs scaled and summed into the residual, then a gated MLP of
21,504; fourteen muP multipliers are constants of the program.  Here the same
layer at a small size on the CPU, float32, seeded random weights, with every
ratio kept: width 64, 10 query heads over 2 K/V heads of 8 (5 a K/V head),
4 state-space heads of 8 over a state of 16 (twice the head size) in 2
groups, an MLP of 96, 3 layers, and all fourteen multipliers away from 1.
The reference is ``chipbench/reference/falcon_h1.py`` (plain ``jax.numpy``:
the recurrence token by token, attention dense), the same file the cell
``serve-falconh1-reason`` verifies against on the chip at the published
widths.

Tolerances.  Program and reference compute the same float32 mathematics in
another order (a chunked scan against a recurrence, grouped query rows
against repeated keys), so they agree to a few float32 roundings of logits of
size ~1: 2e-5 (ISSUE 44).  The chunked scan against the token-by-token one:
1e-4 relative.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn, serve
from tpu_dist.models import FalconH1LM
from tpu_dist.nn.mamba import ssd_chunked, ssd_step

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dict(
    vocab_size=211, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=10, num_key_value_heads=2, head_dim=8,
    intermediate_size=96, mamba_n_heads=4, mamba_d_head=8, mamba_d_ssm=32,
    mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=16,
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
    attention_in_multiplier=0.8, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    ssm_out_multiplier=0.08838834764831845,
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    rope_theta=100000000000, rms_norm_eps=1e-5, max_position_embeddings=256)
SCALARS = ("embedding_multiplier", "lm_head_multiplier",
           "attention_in_multiplier", "attention_out_multiplier",
           "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")
ATOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "falcon_h1_reference", os.path.join(ROOT, "chipbench", "reference",
                                            "falcon_h1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _model(cfg=CFG, **over):
    kw = dict(vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
              depth=cfg["num_hidden_layers"],
              num_heads=cfg["num_attention_heads"],
              num_kv_heads=cfg["num_key_value_heads"],
              head_dim=cfg["head_dim"], mlp_hidden=cfg["intermediate_size"],
              mamba_heads=cfg["mamba_n_heads"],
              mamba_head_dim=cfg["mamba_d_head"],
              mamba_inner_dim=cfg["mamba_d_ssm"],
              mamba_state_dim=cfg["mamba_d_state"],
              mamba_groups=cfg["mamba_n_groups"],
              mamba_conv_kernel=cfg["mamba_d_conv"],
              mamba_chunk_size=cfg["mamba_chunk_size"],
              ssm_multipliers=cfg["ssm_multipliers"],
              mlp_multipliers=cfg["mlp_multipliers"],
              rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
              max_seq_len=cfg["max_position_embeddings"],
              **{name: cfg[name] for name in SCALARS})
    return FalconH1LM(**dict(kw, **over))


def _perturbed(params):
    """Norm weights and ``D`` start at one: perturb every vector so a wrong
    mapping shows."""
    return jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim == 1 else a, params)


@pytest.fixture(scope="module")
def program():
    model = _model()
    return model, _perturbed(model.init(jax.random.key(11)))


def _ref_logits(params, seq, cfg=CFG):
    return np.asarray(REF.forward(cfg, REF.stack_params(cfg, params),
                                  jnp.asarray(seq)[None])[0])


# -- the model -----------------------------------------------------------------

def test_every_layer_holds_both_mixers_under_their_own_paths(program):
    model, params = program
    assert isinstance(model.block0.attn, nn.ParallelMixer)
    assert [type(m).__name__ for m in model.block0.attn.mixers()] == [
        "MultiheadSelfAttention", "Mamba2"]
    # both parts of every layer, in order
    assert [m._path for m in model._mixers()] == [
        f"block{i}.attn.{part}" for i in range(3)
        for part in ("attention", "ssm")]
    assert set(params["block0.attn.attention"]) == {"qkv_weight",
                                                    "out_weight"}
    assert params["block0.attn.attention"]["qkv_weight"].shape == (
        64, 80 + 2 * 16)
    ssm = params["block2.attn.ssm"]
    assert set(ssm) == {"in_weight", "conv_weight", "conv_bias", "A_log",
                        "dt_bias", "D", "norm_weight", "out_weight"}
    # [z | x | B | C | dt]; one convolution over [x | B | C], with a bias
    assert ssm["in_weight"].shape == (64, 32 + 32 + 32 + 32 + 4)
    assert ssm["conv_weight"].shape == (96, 4)
    assert ssm["conv_bias"].shape == (96,)
    assert ssm["A_log"].shape == ssm["dt_bias"].shape == ssm["D"].shape == (
        4,)
    assert ssm["norm_weight"].shape == (32,)
    assert ssm["out_weight"].shape == (32, 64)
    assert "block0.attn" not in params      # the composite keeps nothing
    assert params["block0.mlp.gate"]["weight"].shape == (64, 96)
    assert "bias" not in params["head"] and "pos" not in params


def test_the_lists_may_arrive_as_comma_separated_text():
    """How a configuration file whose harness hands scalars carries them."""
    model = _model(ssm_multipliers="0.5,0.25,2,4,8", mlp_multipliers="3,0.5")
    assert model.block0.attn.ssm.multipliers == (0.5, 0.25, 2.0, 4.0, 8.0)
    assert (model.block0.mlp.gate_multiplier,
            model.block0.mlp.down_multiplier) == (3.0, 0.5)
    with pytest.raises(ValueError, match="holds 5 numbers"):
        _model(ssm_multipliers="1,2")


@pytest.mark.parametrize("flag", ["attention_bias", "mlp_bias",
                                  "mamba_proj_bias", "mamba_norm_before_gate"])
def test_a_layer_other_than_the_published_one_is_refused(flag):
    with pytest.raises(NotImplementedError, match="published Falcon-H1"):
        _model(**{flag: True})
    with pytest.raises(ValueError, match="mamba_inner_dim"):
        _model(mamba_inner_dim=48)


def test_the_seeded_matrices_are_divided_by_what_scales_their_product():
    """``init``: the same key through a model without multipliers gives the
    same draws, each times the multiplier(s) of its product."""
    ones = {name: 1.0 for name in SCALARS}
    plain = _model(ssm_multipliers=[1.0] * 5, mlp_multipliers=[1.0] * 2,
                   **ones).init(jax.random.key(3))
    drawn = _model().init(jax.random.key(3))
    same = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6)
    same(drawn["tok"]["weight"] * CFG["embedding_multiplier"],
         plain["tok"]["weight"])
    same(drawn["head"]["weight"] * CFG["lm_head_multiplier"],
         plain["head"]["weight"])
    qkv, was = (p["block1.attn.attention"]["qkv_weight"]
                for p in (drawn, plain))
    same(qkv[:, :80] * 0.8, was[:, :80])
    same(qkv[:, 80:96] * 0.8 * CFG["key_multiplier"], was[:, 80:96])
    same(qkv[:, 96:] * 0.8, was[:, 96:])
    same(drawn["block1.attn.attention"]["out_weight"] * 0.0375,
         plain["block1.attn.attention"]["out_weight"])
    w_in, was = (p["block1.attn.ssm"]["in_weight"] for p in (drawn, plain))
    for lo, hi, m in zip((0, 32, 64, 96, 128), (32, 64, 96, 128, 132),
                         CFG["ssm_multipliers"]):
        same(w_in[:, lo:hi] * 0.25 * m, was[:, lo:hi])
    same(drawn["block1.attn.ssm"]["out_weight"] * CFG["ssm_out_multiplier"],
         plain["block1.attn.ssm"]["out_weight"])
    for name, m in zip(("gate", "down"), CFG["mlp_multipliers"]):
        same(drawn[f"block1.mlp.{name}"]["weight"] * m,
             plain[f"block1.mlp.{name}"]["weight"])
    for path, name in (("block1.mlp.up", "weight"),
                       ("block1.attn.ssm", "conv_weight"),
                       ("block1.attn.ssm", "A_log")):
        np.testing.assert_array_equal(drawn[path][name], plain[path][name])


def test_forward_logits_match_the_reference(program):
    model, params = program
    tokens = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 90))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for b in range(2):
        np.testing.assert_allclose(got[b], _ref_logits(params, tokens[b]),
                                   rtol=0, atol=ATOL)


def test_the_new_arguments_default_to_no_operation():
    """Each multiplier at its default adds nothing to the traced program:
    every other model's programs are the parent's."""
    def muls(module, *shape, **kw):
        x = jnp.ones(shape, kw.pop("dtype", jnp.float32))
        params = module.init(jax.random.key(0))
        jaxpr = jax.make_jaxpr(lambda p, x: module.apply(p, x))(params, x)
        return str(jaxpr).count(" mul ")

    assert muls(nn.GatedMLP(8, 16, 0.5, 0.25), 2, 8) \
        == muls(nn.GatedMLP(8, 16), 2, 8) + 2
    attn = lambda **kw: nn.MultiheadSelfAttention(8, 2, num_kv_heads=1, **kw)
    assert muls(attn(key_multiplier=0.5), 1, 4, 8) == muls(attn(), 1, 4, 8) + 1
    mamba = lambda **kw: nn.Mamba2(8, 2, 4, 4, **kw)
    assert muls(mamba(multipliers=(2, 2, 2, 2, 2)), 1, 4, 8) \
        == muls(mamba(), 1, 4, 8) + 1
    both = lambda scales: nn.ParallelMixer(a=(nn.Linear(8, 8), *scales),
                                           b=nn.Linear(8, 8))
    assert muls(both((0.5, 0.25)), 2, 8) == 2 and muls(both(()), 2, 8) == 0
    lm = lambda **kw: _model(**dict({name: 1.0 for name in SCALARS},
                                    ssm_multipliers=[1.0] * 5,
                                    mlp_multipliers=[1.0] * 2, depth=1, **kw))
    assert muls(lm(embedding_multiplier=2.0, lm_head_multiplier=0.5),
                1, 4, dtype=jnp.int32) == muls(lm(), 1, 4,
                                               dtype=jnp.int32) + 2


# -- the recurrence ------------------------------------------------------------

def _recurrence_inputs(length, b=2, h=4, p=8, g=2, n=16):
    """From numpy: a draw on the device compiles once a shape."""
    rng = np.random.default_rng(length)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                        jnp.float32)
    x = normal(b, length, h, p)
    dt = jax.nn.softplus(normal(b, length, h) - 1.0)
    a = -jnp.exp(jnp.asarray(rng.uniform(0.0, 2.5, h), jnp.float32))
    return (normal(b, h, p, n), x, dt, a, normal(b, length, g, n),
            normal(b, length, g, n), normal(h))


def _token_by_token(s0, x, dt, a, bm, cm, d):
    def token(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        y, s = ssd_step(s, x_t, dt_t, a, b_t, c_t, d)
        return s, y

    state, y = jax.lax.scan(token, s0, tuple(
        jnp.moveaxis(m, 1, 0) for m in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1), state


def _close(got, want, rel=1e-4):
    assert np.isfinite(got).all()
    assert float(jnp.abs(got - want).max()) <= rel * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("length", range(1, 3 * 8 + 2))
def test_chunked_scan_equals_the_recurrence_at_every_length(length):
    """Every length from 1 to three chunks and one (chunks of 8 here), from
    a state that is not zero: 1e-4 relative, output and state."""
    args = _recurrence_inputs(length)
    want_y, want_state = _token_by_token(*args)
    y, state = jax.jit(lambda *a: ssd_chunked(*a, chunk=8))(*args)
    _close(y, want_y)
    _close(state, want_state)


@pytest.mark.parametrize("length", [127, 128, 129, 385])
def test_chunked_scan_at_the_published_chunk(length):
    args = _recurrence_inputs(length)
    want_y, want_state = _token_by_token(*args)
    y, state = jax.jit(ssd_chunked)(*args)
    _close(y, want_y)
    _close(state, want_state)


@pytest.mark.parametrize("real", [1, 7, 8, 9, 20])
def test_a_no_op_position_leaves_the_state_untouched(real):
    """``dt = 0`` (nobody's position): the chunked scan over a prompt padded
    with such positions ends in the state after its last real one."""
    s0, x, dt, a, bm, cm, d = _recurrence_inputs(30)
    dt = dt.at[:, real:].set(0.0)
    cut = lambda m: m[:, :real]
    chunked = jax.jit(lambda *args: ssd_chunked(*args, chunk=8))
    y_short, short = chunked(s0, cut(x), cut(dt), a, cut(bm), cut(cm), d)
    y, padded = chunked(s0, x, dt, a, bm, cm, d)
    np.testing.assert_allclose(padded, short, rtol=0, atol=2e-5)
    np.testing.assert_allclose(y[:, :real], y_short, rtol=0, atol=2e-5)
    # and the one-token form: a row with dt = 0 keeps its state bit for bit
    _, kept = ssd_step(s0, x[:, 0], jnp.zeros_like(dt[:, 0]), a, bm[:, 0],
                       cm[:, 0], d)
    np.testing.assert_array_equal(kept, s0)


def test_a_group_serves_consecutive_heads():
    """Heads ``[g H / G, (g + 1) H / G)`` read group ``g``'s B and C: the
    state of head 0 does not move with group 1's B, that of head 3 does."""
    s0, x, dt, a, bm, cm, d = _recurrence_inputs(5)
    other = bm.at[:, :, 1].add(1.0)
    for form in (lambda b_: ssd_chunked(s0, x, dt, a, b_, cm, d, chunk=8)[1],
                 lambda b_: ssd_step(s0, x[:, 0], dt[:, 0], a, b_[:, 0],
                                     cm[:, 0], d)[1]):
        was, now = form(bm), form(other)
        np.testing.assert_array_equal(now[:, :2], was[:, :2])
        assert not np.allclose(now[:, 2:], was[:, 2:])


def test_decay_at_initialisation_spans_short_and_long_memories():
    layer = nn.Mamba2(64, 32, 8, 16, num_groups=2)
    p = layer.init(jax.random.key(3))[""]
    dt = jax.nn.softplus(p["dt_bias"])
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 0.1001
    a = -np.exp(np.asarray(p["A_log"]))
    assert -16 <= a.min() and a.max() <= -1
    decay = np.exp(np.asarray(dt) * a)
    assert decay.max() > 0.95 and decay.min() < 0.8
    np.testing.assert_array_equal(p["D"], 1.0)


# -- serving -------------------------------------------------------------------

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


def _pool(model, slots=4, max_len=256):
    return model.init_slot_cache(slots, max_len)


def test_a_slot_holds_columns_and_a_whole_state_for_each_layer(program):
    model, _ = program
    pool = _pool(model)
    assert list(pool) == [f"block{i}.attn.{part}" for i in range(3)
                          for part in ("attention", "ssm")]
    assert {name: leaf.shape for name, leaf in
            pool["block1.attn.attention"].items()} == {
        "k": (4, 2, 8, 256), "v": (4, 2, 8, 256)}
    assert {name: (leaf.shape, leaf.dtype) for name, leaf in
            model.init_slot_cache(4, 256, jnp.bfloat16)[
                "block1.attn.ssm"].items()} == {
        "state": ((4, 4, 8, 16), jnp.float32),
        "conv": ((4, 3 * 96), jnp.bfloat16)}
    # by hand: 3 layers x (4 x 8 x 16 float32 + a tail of 3 x 96 float32),
    # whatever the context; 2 K/V heads x 8 x (k and v) float32 a position
    assert nn.cache.slot_bytes(pool) == (3 * (2048 + 1152), 3 * 2 * 8 * 2 * 4)
    assert nn.cache.extent(pool) == (256, jnp.float32)
    assert len(nn.cache.kv_entries(pool)) == 3
    assert nn.cache.state_leaves(pool) == [
        f"block{i}.attn.ssm.{name}" for i in range(3)
        for name in ("state", "conv")]
    template = nn.cache.token_template(pool)
    assert template["block0.attn.attention"]["k"] == ((2, 8), np.float32)
    assert template["block0.attn.ssm"]["state"] == ((4, 8, 16), np.float32)
    assert model.slot_decode_kernel(pool) is False      # a CPU run
    assert model.slot_state_kernel(pool) is False       # no such kernel
    assert model.prefill_scan_kernel(pool, 64) is False
    assert model.prefill_attention_facts(64) == {
        "kernel": False, "heads": 0, "pairs_executed": 0}


@pytest.mark.parametrize("bucket", [64, 128])
def test_prefill_then_decode_match_the_reference_position_by_position(
        program, bucket):
    """Through the slot cache, two busy slots and a free one beside the
    request's: every position's logits are the reference's full forward's,
    whatever the bucket."""
    model, params = program
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, CFG["vocab_size"], 45)
    with jax.default_matmul_precision("highest"):
        pool, others = _pool(model), {}
        for slot, n in ((0, 30), (3, 77)):
            other = rng.integers(0, CFG["vocab_size"], n)
            rows, _, pool = _serve_one(model, params, other, 1, slot, pool,
                                       bucket=128)
            others[slot] = (int(np.argmax(rows[0])), n)
        rows, toks, _ = _serve_one(model, params, prompt, 12, 2, pool,
                                   bucket=bucket, others=others)
    full = np.concatenate([prompt, toks])
    ref = _ref_logits(params, full)[len(prompt) - 1:-1]
    np.testing.assert_allclose(rows, ref, rtol=0, atol=ATOL)


def test_a_request_does_not_depend_on_its_bucket_or_its_neighbours(program):
    model, params = program
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, CFG["vocab_size"], 41)
    with jax.default_matmul_precision("highest"):
        base, toks, _ = _serve_one(model, params, prompt, 8, 1,
                                   _pool(model), bucket=64)
        wide, toks_wide, _ = _serve_one(model, params, prompt, 8, 1,
                                        _pool(model), bucket=128)
        pool, others = _pool(model), {}
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
    (the padding token read as the request's) moves the first logits by far
    more."""
    model, params = program
    prompt = np.random.default_rng(3).integers(0, CFG["vocab_size"], 41)
    padded = np.full(64, 5, np.int32)
    padded[:41] = prompt
    prefill = jax.jit(model.prefill_into_slot)
    right, *_ = prefill(params, padded, 41, 0, _pool(model))
    wrong, *_ = prefill(params, padded, 42, 0, _pool(model))
    assert np.abs(np.asarray(right) - np.asarray(wrong)).max() > 100 * ATOL


def test_a_reused_slot_carries_nothing_over(program):
    """A slot that held a longer request serves the next, shorter one as a
    fresh pool does: the state and the tail are written entire at
    admission, and the columns past the new prompt are never read."""
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
    np.testing.assert_allclose(reused, fresh, rtol=0, atol=ATOL)


def test_free_slots_keep_their_state_through_a_decode_step(program):
    model, params = program
    cache = jax.tree.map(lambda a: a + 1, _pool(model))
    lengths = np.array([0, 7, 0, 0], np.int32)
    _, after, _ = jax.jit(model.decode_step)(
        params, np.array([0, 3, 0, 0], np.int32), lengths, cache)
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
    assert seen == {"state", "conv"}


def test_generate_serves_the_reference_tokens(program):
    """``generate()`` runs on the same two methods: its tokens are the slot
    path's, and each is the reference's largest logit at its position."""
    model, params = program
    prompt = np.random.default_rng(6).integers(0, CFG["vocab_size"], (1, 23))
    with jax.default_matmul_precision("highest"):
        out = np.asarray(model.generate(params, jnp.asarray(prompt), 6))
        _, toks, _ = _serve_one(model, params, prompt[0], 6, 0,
                                _pool(model, slots=2), bucket=32)
    assert out[0, 23:].tolist() == toks
    ref = _ref_logits(params, out[0])[22:-1]
    assert (ref.max(-1) - ref[np.arange(6), out[0, 23:]]).max() <= ATOL


# -- the engine: a layer that is a recurrent layer AND an attention layer -------

def test_slot_engine_serves_the_reference_tokens_and_counts_by_hand(program):
    """Through ``SlotEngine`` (bucketed prefill, the launch-ahead halves, two
    requests side by side): every served token is the reference's largest
    logit at its position; and ``stats()["state"]``, ``["decode_need"]``,
    ``["decode_attn"]`` and ``["prefill_scan"]`` against hand counts for a
    slot that holds, in EACH of 3 layers, K/V columns and a whole state."""
    model, params = program
    engine = serve.SlotEngine(model, params, num_slots=3, max_len=128,
                              min_bucket=32)
    state_bytes = 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    per_pos = 3 * 2 * 8 * 2 * 4
    assert nn.cache.slot_bytes(engine.cache) == (state_bytes, per_pos)
    need = engine._need
    # every layer counts under both heads: 10 query heads of 8, q.k and p.v;
    # 4 states of 8 x 16: decay, rank-one update, contraction with C
    assert need["attend_flops"] == 3 * 2 * 10 * 2 * 8
    assert need["state_flops"] == 3 * 5 * 4 * 8 * 16
    size = lambda tree: sum(int(a.size) for a in jax.tree.leaves(tree))
    fixed = size(params) - 211 * 64           # all but the gathered table
    assert need["fixed_params"] == fixed and need["experts"] == {}
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
                           "kv_bytes": per_pos * positions,
                           "steps": 8, "kernel_steps": 0}
    dn = st["decode_need"]
    assert (dn["steps"], dn["rows"], dn["positions"]) == (8, 16, positions)
    assert dn["cache_bytes"] == (st["state"]["state_bytes"]
                                 + st["state"]["kv_bytes"])
    assert dn["weight_bytes"] == 8 * 4 * fixed
    assert dn["flops"] == (2 * fixed * 16 + 3 * 2 * 10 * 2 * 8 * positions
                           + 3 * 5 * 4 * 8 * 16 * 16)
    # a CPU run's dense branch reads the whole pool: one block of 128
    # columns a slot a step, where the two busy slots held one each
    assert st["decode_attn"] == {"kv_blocks_read": 16, "kv_blocks_pool": 24,
                                 "steps": 8, "block": 128, "kernel": False}
    assert st["prefill_scan"] == {"prefills": 2, "kernel_prefills": 0}
    assert st["prefill_attn"]["kernel_prefills"] == 0
    assert "moe" not in st


def _steps_on(model, params, impl, prompts, steps=4):
    """Under ``impl``: ``prompts`` prefilled into slots 0 and 2 of a pool of
    three (slot 1 stays FREE), then ``steps`` greedy decode steps through a
    program traced afresh (a trace is cached by the function traced).
    Returns the busy rows' logits a step and the pool before and after."""
    with nn.attention_impl(impl):
        pool = _pool(model, slots=3, max_len=128)
        prefill = jax.jit(lambda *a: model.prefill_into_slot(*a))
        decode = jax.jit(lambda *a: model.decode_step(*a))
        tokens, lengths = np.zeros(3, np.int32), np.zeros(3, np.int32)
        for slot, prompt in zip((0, 2), prompts):
            padded = np.full(64, 5, np.int32)
            padded[:len(prompt)] = prompt
            row, pool, _ = prefill(params, padded, len(prompt), slot, pool)
            tokens[slot], lengths[slot] = int(np.argmax(row)), len(prompt)
        before, rows = jax.tree.map(np.asarray, pool), []
        for _ in range(steps):
            logits, pool, _ = decode(params, tokens, lengths, pool)
            rows.append(np.asarray(logits)[[0, 2]])
            tokens[[0, 2]] = np.argmax(rows[-1], axis=-1)
            lengths[[0, 2]] += 1
    return np.stack(rows), before, jax.tree.map(np.asarray, pool)


def test_the_grouped_kernel_serves_the_dense_branchs_tokens_and_logits(
        program):
    """ISSUE 45: ten query heads over two K/V heads through the grouped
    slot-decode kernel (``attention_impl("flash")``, interpreted), one slot
    free: the dense branch's logits step by step, the engine's tokens, the
    free slot's K/V rows untouched, and ``stats()["decode_attn"]`` saying
    which branch the decode program was built on."""
    model, params = program
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, CFG["vocab_size"], n) for n in (21, 50)]
    with jax.default_matmul_precision("highest"):
        want, _, _ = _steps_on(model, params, "dense", prompts)
        got, before, after = _steps_on(model, params, "flash", prompts)
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    for i in range(3):
        entry = f"block{i}.attn.attention"
        for name in ("k", "v"):
            np.testing.assert_array_equal(after[entry][name][1],
                                          before[entry][name][1])
            assert not np.array_equal(after[entry][name][0],
                                      before[entry][name][0])
    served = {}
    for impl in ("dense", "flash"):
        toks = {0: [], 1: []}
        with nn.attention_impl(impl):
            engine = serve.SlotEngine(model, params, num_slots=3,
                                      max_len=128, min_bucket=32)
            for i, prompt in enumerate(prompts):
                engine.launch_admit(serve.Request(
                    prompt, 5,
                    on_token=lambda _, tok, i=i: toks[i].append(tok)))
                engine.settle()
            while not engine.idle():
                if engine.launch_step():
                    engine.settle()
                else:
                    engine.collect_all()
        served[impl] = toks, engine.stats()["decode_attn"]
    assert served["flash"][0] == served["dense"][0]
    assert served["flash"][1] == dict(served["dense"][1], kernel=True)
    assert served["dense"][1]["kernel"] is False


def _rows(model, length=8):
    return jax.tree.map(np.asarray, model.init_slot_cache(1, length))


@pytest.mark.parametrize("mover", ["prefix", "kvtransfer", "disagg"])
def test_the_movers_refuse_the_pool_by_the_state_leafs_name(program, mover):
    from tpu_dist.serve.disagg import DisaggSlotEngine
    model, params = program
    moves = {
        "prefix": lambda: serve.PrefixCache(block_tokens=4).insert(
            np.arange(8), _rows(model), 8),
        "kvtransfer": lambda: serve.KVTransfer(
            None, serve.kv_template(model.init_slot_cache(1, 16))),
        "disagg": lambda: DisaggSlotEngine(
            model, params, kv=None, dispatch_ch=None, arrive_ch=None,
            num_slots=2, max_len=32, rank=0)}
    with pytest.raises(
            NotImplementedError,
            match=r"block0\.attn\.ssm\.(state|conv).*no time axis"):
        moves[mover]()


def test_sharded_serving_refuses_the_layer_by_the_mixers_name(program):
    """A head-divided hybrid is later work: a plain refusal that names the
    composite, not an attribute error on ``block0.attn``."""
    model, _ = program
    with pytest.raises(NotImplementedError,
                       match=r"block0\.attn is a ParallelMixer"):
        serve.ShardedLM(model, 0, 2)


def test_the_pipeline_refuses_the_embedding_and_head_multipliers(program):
    from tpu_dist.parallel import PipelineParallel
    model, _ = program
    with pytest.raises(NotImplementedError, match="embedding_multiplier"):
        PipelineParallel(model, optimizer=None, loss_fn=None,
                         group=type("G", (), {"mesh": type("M", (), {
                             "axis_names": ("pipe",),
                             "shape": {"pipe": 1}})()})())


# -- what the chip's faults are on the CPU ---------------------------------------

def _with(**over):
    return lambda ref, cfg, params: (dict(cfg, **over), params)


def _list(name, i, value):
    return lambda ref, cfg, params: (
        dict(cfg, **{name: [value if j == i else m
                            for j, m in enumerate(cfg[name])]}), params)


def _no_skip(ref, cfg, params):
    zero = lambda path, leaves: (dict(leaves, D=jnp.zeros_like(leaves["D"]))
                                 if path.endswith(".ssm") else leaves)
    return cfg, {path: zero(path, leaves) for path, leaves in params.items()}


def _patched(name, value):
    def plant(ref, cfg, params):
        setattr(ref, name, value)
        return cfg, params
    return plant


FAULTS = {
    **{f"{name}_taken_as_1": _with(**{name: 1.0}) for name in SCALARS},
    **{f"ssm_multipliers_{i}_taken_as_1": _list("ssm_multipliers", i, 1.0)
       for i in range(5)},
    **{f"mlp_multipliers_{i}_taken_as_1": _list("mlp_multipliers", i, 1.0)
       for i in range(2)},
    "attention_branch_dropped": _with(attention_out_multiplier=0.0),
    "ssm_branch_dropped": _with(ssm_out_multiplier=0.0),
    "no_d_skip": _no_skip,
    "norm_over_one_group": _patched("_norm_groups", lambda config: 1),
    "b_and_c_of_interleaved_groups": _patched(
        "_group_of_head",
        lambda config: np.arange(config["mamba_n_heads"])
        % config["mamba_n_groups"]),
    "dt_without_softplus": _patched("_dt_activation", lambda dt: dt),
}


FAULT_TOKENS = np.random.default_rng(7).integers(0, CFG["vocab_size"], 80)


@pytest.fixture(scope="module")
def right_logits(program):
    return _ref_logits(program[1], FAULT_TOKENS)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_mathematics_shows_in_the_logits(program, fault,
                                                        right_logits):
    """Each of the fourteen multipliers taken as 1, the ``D`` skip left
    out, the gated norm over one group of all its numbers, B and C read by
    interleaved groups, either branch dropped and ``dt`` without its
    softplus, planted ONE at a time in a load of the reference of its own:
    the logits move by more than 100 times the tolerance (most by far more:
    the chip's controls plant five of them,
    chipbench/tests/fixture/fault_control_falconh1)."""
    _, params = program
    ref = _reference()
    cfg, planted = FAULTS[fault](ref, CFG, params)
    wrong = np.asarray(ref.forward(cfg, ref.stack_params(cfg, planted),
                                   jnp.asarray(FAULT_TOKENS)[None])[0])
    moved = np.abs(wrong - right_logits).max()
    assert not moved <= 100 * ATOL, moved      # nan counts as moved

"""OLMoE's block through the program, against the plain reference.

The published model (allenai/OLMoE-1B-7B-0125-Instruct) is 16 layers of
width 2048 with 64 SiLU-gated experts of 1024, 8 a token; here the same
block at a small size on the CPU, float32, seeded random weights: dim 64,
4 heads of 16, 8 experts of width 32, 2 a token, 2 layers.  The reference is
``chipbench/reference/olmoe.py`` (plain ``jax.numpy``, every expert computed
densely over every token, no cache), the same file the benchmark cell
``serve-olmoe-docs`` verifies against on the chip at the published widths.

Tolerances.  Program and reference compute the same float32 mathematics in
another order (grouped rows against a dense masked sum, a cached K/V pool
against full attention), so they agree to a few float32 roundings of logits
of size ~2: 2e-5.  What must NOT depend on batch composition is compared
more tightly, and the test says whether that is bitwise.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn, serve
from tpu_dist.models import TransformerLM

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dict(vocab_size=211, hidden_size=64, num_hidden_layers=2,
           num_attention_heads=4, num_experts=8, num_experts_per_tok=2,
           intermediate_size=32, norm_topk_prob=False, rms_norm_eps=1e-5,
           rope_theta=10000, max_position_embeddings=128)
ATOL = 2e-5


def _reference():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "olmoe_reference", os.path.join(ROOT, "chipbench", "reference",
                                        "olmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _model(**over):
    kw = dict(vocab_size=CFG["vocab_size"], dim=CFG["hidden_size"],
              depth=CFG["num_hidden_layers"],
              num_heads=CFG["num_attention_heads"],
              max_seq_len=CFG["max_position_embeddings"],
              num_experts=CFG["num_experts"],
              moe_top_k=CFG["num_experts_per_tok"],
              moe_hidden=CFG["intermediate_size"],
              moe_normalize_gates=CFG["norm_topk_prob"],
              norm_eps=CFG["rms_norm_eps"], rope_theta=CFG["rope_theta"],
              norm="rmsnorm", rope=True, qk_norm=True, attn_bias=False,
              moe_gated=True, moe_dispatch="dropless")
    return TransformerLM(**dict(kw, **over))


@pytest.fixture(scope="module")
def program():
    model = _model()
    params = model.init(jax.random.key(7))
    # norm weights start at one and the head's bias at zero: perturb every
    # vector so a wrong mapping of any of them shows
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim == 1 else a, params)
    return model, params


def _ref_logits(params, seq):
    return np.asarray(REF.forward(CFG, REF.stack_params(CFG, params),
                                  jnp.asarray(seq)[None])[0])


def test_parameters_are_the_published_block(program):
    _, params = program
    assert set(params["block0.mlp"]) == {"router", "w1", "w3", "w2"}
    assert params["block0.mlp"]["w1"].shape == (8, 64, 32)
    assert params["block0.mlp"]["w2"].shape == (8, 32, 64)
    assert set(params["block0.attn"]) == {"qkv_weight", "out_weight",
                                          "q_norm_weight", "k_norm_weight"}
    assert "pos" not in params and set(params["block0.ln1"]) == {"weight"}


def test_forward_logits_match_the_reference(program):
    model, params = program
    tokens = np.random.default_rng(0).integers(0, CFG["vocab_size"], (3, 40))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, jnp.asarray(tokens)))
    for b in range(3):
        np.testing.assert_allclose(got[b], _ref_logits(params, tokens[b]),
                                   rtol=0, atol=ATOL)


def _pool(model, slots=4, max_len=128):
    """The slot pool and, beside it, the routed-row counters."""
    return model.init_slot_cache(slots, max_len), model.init_moe_counters()


def _serve_one(model, params, prompt, n_new, slot, pool, bucket, others=None):
    """Prefill ``prompt`` (padded to ``bucket``) into ``slot`` and decode
    ``n_new`` greedy tokens; ``others`` = {slot: (token, length)} keeps those
    slots decoding beside it.  Returns the logits rows and the tokens."""
    padded = np.zeros(bucket, np.int32)
    padded[:len(prompt)] = prompt
    prefill = jax.jit(model.prefill_into_slot)
    decode = jax.jit(model.decode_step)
    row, *pool = prefill(params, padded, len(prompt), slot, *pool)
    rows, toks = [np.asarray(row)], [int(np.argmax(row))]
    slots = len(jax.tree.leaves(pool[0])[0])
    tokens, lengths = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
    for s, (tok, length) in (others or {}).items():
        tokens[s], lengths[s] = tok, length
    tokens[slot], lengths[slot] = toks[0], len(prompt)
    for _ in range(n_new - 1):
        logits, *pool = decode(params, tokens, lengths, *pool)
        nxt = np.asarray(jnp.argmax(logits, -1))
        rows.append(np.asarray(logits[slot]))
        toks.append(int(nxt[slot]))
        for s in list(others or {}) + [slot]:
            tokens[s], lengths[s] = nxt[s], lengths[s] + 1
    return np.stack(rows), toks, pool


def test_prefill_then_cached_decode_match_the_reference_full_forward(program):
    """Position by position: the pool programs' logits for a prompt of 21 in
    a 32 bucket and 6 decoded tokens equal the reference's full forward over
    the whole sequence."""
    model, params = program
    prompt = np.random.default_rng(1).integers(1, CFG["vocab_size"], 21)
    rows, toks, _ = _serve_one(model, params, prompt, 6, 2, _pool(model), 32)
    want = _ref_logits(params, np.concatenate([prompt, toks]))
    np.testing.assert_allclose(rows, want[len(prompt) - 1:-1], rtol=0,
                               atol=ATOL)


def test_logits_do_not_depend_on_the_other_slots(program):
    """The same request alone in the pool and beside three busy slots.  The
    dropless path gives every routed row its own row of a grouped matmul and
    attention reads only the request's own pool row, so nothing of another
    slot enters the arithmetic; which row of a block the request lands in
    does change, and the CPU's matmul may sum a row's products in another
    order there, so the comparison is to 1e-6 and not bitwise.  With
    capacity routing the difference is a dropped expert: ~1e-1."""
    model, params = program
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, CFG["vocab_size"], 19)
    alone, toks_a, _ = _serve_one(model, params, prompt, 5, 1, _pool(model),
                                  32)
    pool = _pool(model)
    others = {}
    for s in (0, 2, 3):
        p = rng.integers(1, CFG["vocab_size"], 25 + s)
        _, t, pool = _serve_one(model, params, p, 1, s, pool, 32)
        others[s] = (t[0], len(p))
    busy, toks_b, _ = _serve_one(model, params, prompt, 5, 1, pool, 32,
                                 others)
    assert toks_a == toks_b
    np.testing.assert_allclose(busy, alone, rtol=0, atol=1e-6)


def test_logits_do_not_depend_on_the_bucket(program):
    """A prompt of 40 padded to 64 and to 128: the padding rows are routed
    (and counted apart) but reach no real row.  The matmuls' shapes differ
    between the two programs, so 1e-6 and not bitwise."""
    model, params = program
    prompt = np.random.default_rng(3).integers(1, CFG["vocab_size"], 40)
    a, ta, pa = _serve_one(model, params, prompt, 4, 0, _pool(model), 64)
    b, tb, pb = _serve_one(model, params, prompt, 4, 0, _pool(model), 128)
    assert ta == tb
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
    # routed rows: 40 real x 2 experts; padding 24 x 2 and 88 x 2 (one
    # prefill), then 3 free slots x 2 in each of 3 decode steps
    for (_, counters), pad in ((pa, 24), (pb, 88)):
        c = counters["block1.mlp"]
        assert int(c["rows"].sum()) == 40 * 2 + 3 * 2
        assert int(c["pad_rows"]) == pad * 2 + 3 * 3 * 2
        assert int(c["calls"]) == 4


@pytest.fixture(scope="module")
def layer():
    moe = nn.MoELayer(64, 8, hidden=32, top_k=2, normalize_gates=False,
                      dispatch="dropless", gated=True)
    params = moe.init(jax.random.key(11))
    return moe, params


def _ref_moe(params, x, **over):
    p = next(iter(params.values()))
    named = {"router": p["router"], "gate": p["w1"], "up": p["w3"],
             "down": p["w2"]}
    with jax.default_matmul_precision("highest"):
        return np.asarray(REF.moe(dict(CFG, **over), named, x))


def test_every_row_is_computed_when_the_router_sends_all_to_one_expert(layer):
    """Positive inputs and a router that prefers experts 0 and 1 for every
    row: 48 rows each at two experts, none at the other six.  The capacity
    path (1.25 x 12 = 15 slots an expert) drops 33 of each 48."""
    moe, params = layer
    path = next(iter(params))
    router = jnp.zeros((64, 8)).at[:, 0].set(1.0).at[:, 1].set(0.5)
    params = {path: dict(params[path], router=router)}
    x = jnp.abs(jax.random.normal(jax.random.key(5), (48, 64))) + 0.1
    want = _ref_moe(params, x)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(moe.apply(params, x))
        capped = nn.MoELayer(64, 8, hidden=32, top_k=2, normalize_gates=False,
                             dispatch="einsum", gated=True)
        dropped = np.asarray(capped.apply(params, x))
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    assert np.abs(dropped - want).max() > 1e-2      # what a drop looks like


def test_router_weights_are_not_renormalised(layer):
    """``norm_topk_prob`` false: the two selected probabilities are used as
    they are (they sum to well under 1 with 8 experts).  The same layer with
    ``normalize_gates=True`` must NOT match the reference."""
    moe, params = layer
    x = jax.random.normal(jax.random.key(6), (40, 64))
    want = _ref_moe(params, x)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(moe.apply(params, x))
        renorm = nn.MoELayer(64, 8, hidden=32, top_k=2, normalize_gates=True,
                             dispatch="dropless", gated=True)
        other = np.asarray(renorm.apply(params, x))
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
    assert np.abs(other - want).max() > 1e-2
    np.testing.assert_allclose(other, _ref_moe(params, x, norm_topk_prob=True),
                               rtol=0, atol=ATOL)


@pytest.mark.parametrize("dispatch", ["einsum", "gather"])
def test_gated_expert_is_the_same_through_every_dispatch(layer, dispatch):
    """With capacity for every row the capacity paths compute the same gated
    expert; and the dropless path's gradients (grouped matmuls without a
    bias) equal theirs."""
    moe, params = layer
    x = jax.random.normal(jax.random.key(8), (32, 64))
    other = nn.MoELayer(64, 8, hidden=32, top_k=2, normalize_gates=False,
                        capacity_factor=4.0, dispatch=dispatch, gated=True)
    loss = lambda layer: lambda p, x: jnp.sum(jnp.square(layer.apply(p, x)))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(moe.apply(params, x),
                                   other.apply(params, x), rtol=0, atol=ATOL)
        g_a = jax.grad(loss(moe), argnums=(0, 1))(params, x)
        g_b = jax.grad(loss(other), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g_a), jax.tree.leaves(g_b)):
        # gradients of a sum of squares reach ~5e2: relative, float32
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


def test_qk_norm_is_over_the_whole_projection_before_the_head_split():
    """RMSNorm over all 64 features of q and of k, then the split into 4
    heads of 16: the module equals that form and does NOT equal the per-head
    form (an RMS over each head's 16)."""
    attn = nn.MultiheadSelfAttention(64, 4, bias=False, causal=True,
                                     qk_norm=True, qk_norm_eps=1e-5)
    params = attn.init(jax.random.key(9))
    path = next(iter(params))
    w = {k: v + 0.1 * jax.random.normal(jax.random.key(v.size), v.shape)
         for k, v in params[path].items()}
    x = jax.random.normal(jax.random.key(10), (2, 12, 64))

    def manual(per_head):
        q, k, v = jnp.split(x @ w["qkv_weight"], 3, axis=-1)
        def norm(a, weight):
            if per_head:
                a = a.reshape(2, 12, 4, 16)
                weight = weight.reshape(4, 16)
            a = a * jax.lax.rsqrt(jnp.square(a).mean(-1, keepdims=True) + 1e-5)
            return (a * weight).reshape(2, 12, 4, 16)
        q, k = norm(q, w["q_norm_weight"]), norm(k, w["k_norm_weight"])
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
        s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                       v.reshape(2, 12, 4, 16))
        return np.asarray(o.reshape(2, 12, 64) @ w["out_weight"])

    with jax.default_matmul_precision("highest"):
        got = np.asarray(attn.apply({path: w}, x))
        np.testing.assert_allclose(got, manual(per_head=False), rtol=0,
                                   atol=ATOL)
        assert np.abs(got - manual(per_head=True)).max() > 1e-2


def test_slot_engine_serves_the_reference_argmax_and_counts_rows(program):
    """Through SlotEngine + Scheduler with four requests in flight: every
    served token is the reference's argmax at its position (margin <= 1e-4,
    a near-tie may flip on summation order), and ``stats()["moe"]`` holds
    the routed rows: requests' rows per expert, padding apart, per pool
    program; ``reset_stats()`` starts them again."""
    model, params = program
    eng = serve.SlotEngine(model, params, num_slots=4, max_len=128,
                           min_bucket=16)
    sched = serve.Scheduler(eng)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, CFG["vocab_size"], n) for n in (9, 23, 40, 17)]
    try:
        handles = [sched.submit(list(map(int, p)), max_new_tokens=5)
                   for p in prompts]
        outs = [h.wait_done(300.0) for h in handles]
        moe = eng.stats()["moe"]
        programs = eng.stats()["pipeline"]["launches"]["prefill"]
        eng.reset_stats()
        zero = eng.stats()["moe"]
    finally:
        sched.close()
    for prompt, out in zip(prompts, outs):
        want = _ref_logits(params, np.concatenate([prompt, out]))
        for j, tok in enumerate(out):
            row = want[len(prompt) - 1 + j]
            assert float(row.max() - row[tok]) <= 1e-4
    layers, k = CFG["num_hidden_layers"], CFG["num_experts_per_tok"]
    pre, dec = moe["by_phase"]["prefill"], moe["by_phase"]["decode"]
    assert pre["rows"] == sum(map(len, prompts)) * k * layers
    # a program of a bucket takes as many prompts as the pool's 128
    # positions hold of it (ISSUE 48), so each runs 128 rows, whoever shared
    # it: what is no request's is padding, an absent prompt's rows too
    assert all(eng.prefill_width(b) * b == 128 for b in eng.buckets)
    assert 1 <= programs <= 4
    assert pre["rows"] + pre["pad_rows"] == programs * 128 * k * layers
    assert pre["calls"] == programs * layers
    # 4 tokens a request come from decode steps (the first from prefill)
    assert dec["rows"] == 4 * 4 * k * layers
    assert (dec["rows"] + dec["pad_rows"]) == dec["calls"] * 4 * k
    assert len(moe["rows_per_expert"]) == CFG["num_experts"]
    assert sum(moe["rows_per_expert"]) == moe["rows"] == (pre["rows"]
                                                          + dec["rows"])
    assert 0 < pre["experts_hit"] <= pre["calls"] * CFG["num_experts"]
    assert zero["rows"] == zero["pad_rows"] == zero["calls"] == 0
    assert not any(zero["rows_per_expert"])


def test_a_fixed_grouping_counts_its_rows_and_padding_exactly(program):
    """The same four prompts through ``launch_group`` by hand, so that who
    shares a program is the test's and not the loop's timing: three
    programs (the 16 bucket's one, the 32 bucket's two together, the 64
    bucket's one), each of 128 rows whatever it carries; a request's
    positions are ``rows``, everything else of the program ``pad_rows``."""
    model, params = program
    eng = serve.SlotEngine(model, params, num_slots=4, max_len=128,
                           min_bucket=16)
    rng = np.random.default_rng(4)
    lengths = (9, 23, 40, 17)
    reqs = [serve.Request(rng.integers(1, CFG["vocab_size"], n).astype(
        np.int32), 1, req_id=i + 1) for i, n in enumerate(lengths)]
    groups = [[reqs[0]], [reqs[1], reqs[3]], [reqs[2]]]
    assert [eng.launch_group(g) for g in groups] == [[0], [1, 2], [3]]
    eng.collect_all()
    layers, k = CFG["num_hidden_layers"], CFG["num_experts_per_tok"]
    pre = eng.stats()["moe"]["by_phase"]["prefill"]
    assert pre["calls"] == 3 * layers
    assert pre["rows"] == sum(lengths) * k * layers
    assert pre["pad_rows"] == (3 * 128 - sum(lengths)) * k * layers
    assert eng.stats()["pipeline"]["prefill_absent_rows"] == 7 + 2 + 1
    assert eng.stats()["prefill_attn"]["prefills"] == 4


def test_a_dense_model_keeps_no_moe_counters():
    model = TransformerLM(97, dim=32, depth=1, num_heads=2, max_seq_len=32)
    assert model.init_moe_counters() == {}
    eng = serve.SlotEngine(model, model.init(jax.random.key(0)), num_slots=2,
                           max_len=32)
    assert "moe" not in eng.stats()

"""Kimi K2 (the DeepSeek-V3 block) through the slot engine, on the CPU at
small sizes with seeded weights (ISSUE 32).

The program's float32 logits against the plain reference
(chipbench/reference/kimi_k2.py) for a full forward and for prefill + decode
through a slot pool; the two attention paths on the same latent; bucket
independence and slot isolation of the latent columns; the sigmoid router
with its selection bias; the shares of an expert-parallel deployment adding
up to the uncut layer; YaRN against hand-computed values; nn/cache.py and the
host-side movers on a latent-only cache; ``stats()["decode_need"]`` against a
hand count; and the two routed models the benchmark already serves,
bit-identical to the parent commit's served logits.
"""

import hashlib
import importlib.util
import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn, serve
from tpu_dist.models import KimiK2LM, Qwen3NextLM, TransformerLM

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = dict(vocab_size=211, hidden_size=64, num_hidden_layers=3,
           num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
           intermediate_size=96, first_k_dense_replace=1, moe_layer_freq=1,
           n_routed_experts=16, router_num_experts=16, expert_offset=0,
           num_experts_per_tok=4, moe_intermediate_size=32,
           n_shared_experts=1, norm_topk_prob=True,
           routed_scaling_factor=2.827, scoring_func="sigmoid",
           topk_method="noaux_tc", n_group=1, topk_group=1,
           rope_theta=50000, rms_norm_eps=1e-6,
           rope_scaling=dict(beta_fast=1, beta_slow=1, factor=32, mscale=1,
                             mscale_all_dim=1,
                             original_max_position_embeddings=16,
                             type="yarn"),
           max_position_embeddings=128)
SHARE = dict(CFG, n_routed_experts=4, expert_offset=4)
ATOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location(
        "kimi_k2_reference", os.path.join(ROOT, "chipbench", "reference",
                                          "kimi_k2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _model(cfg=CFG, **over):
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
    return KimiK2LM(**dict(kw, **over))


def _perturbed(params, seed=7):
    """Norm weights start at one: perturb every vector (the router's bias
    too) so a wrong mapping shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def served():
    """The model that holds experts 4-7 of 16, its parameters and a
    40-token sequence with the reference's logits."""
    model = _model(SHARE)
    params = _perturbed(model.init(jax.random.key(0)))
    seq = np.asarray(jax.random.randint(jax.random.key(1), (40,), 0, 211))
    want = np.asarray(REF.forward(SHARE, REF.stack_params(SHARE, params),
                                  seq[None])[0])
    return model, params, seq, want


# -- the model against the reference ------------------------------------------

def test_layer_kinds_follow_the_one_published_scalar():
    assert _model().layer_kinds == ["dense", "moe", "moe"]
    assert _model(first_k_dense_replace=2).layer_kinds == [
        "dense", "dense", "moe"]
    model = _model()
    assert isinstance(model.block0.mlp, nn.GatedMLP)
    assert isinstance(model.block1.mlp, nn.MoELayer)
    assert all(isinstance(m, nn.MultiheadLatentAttention)
               for m in model._mixers())
    p = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    assert set(p["block1.mlp"]) == {"router", "router_bias", "w1", "w2",
                                    "w3", "shared_w1", "shared_w2",
                                    "shared_w3"}           # no shared gate
    assert p["block1.mlp"]["router_bias"].shape == (16,)
    assert p["block0.attn"]["kv_a_weight"].shape == (64, 16 + 8)
    assert p["head"]["weight"].shape == (64, 211) and "bias" not in p["head"]


@pytest.mark.parametrize("cfg", [CFG, SHARE], ids=["whole", "share"])
def test_full_forward_is_the_references(cfg):
    model = _model(cfg)
    params = _perturbed(model.init(jax.random.key(0)))
    toks = jax.random.randint(jax.random.key(1), (2, 40), 0, 211)
    got = model.apply(params, toks)
    want = REF.forward(cfg, REF.stack_params(cfg, params), toks)
    np.testing.assert_allclose(got, want, atol=ATOL)


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
    # the counters told the request's rows from the free slots'
    rows = sum(int(c["rows"].sum()) for c in jax.device_get(moe).values())
    assert rows == 2 * 4 * (25 + 15)


def test_a_prefix_hit_prefills_the_suffix_over_cached_latent(served):
    """The scalar-index path at a TRACED position: 16 cached columns, the
    other 9 tokens prefilled over them, equals the whole prompt's
    prefill."""
    model, params, seq, want = served
    prompt = np.zeros(32, np.int32)
    prompt[:25] = seq[:25]
    _, rows, _ = model.prefill_rows(params, prompt, 25, 64)
    prefix = nn.cache.pad_time(jax.tree.map(
        lambda a: np.asarray(nn.cache.time_slice(a, 0, 16)), rows), 64)
    suffix = np.zeros(16, np.int32)
    suffix[:9] = seq[16:25]
    logits, again, _ = jax.jit(
        lambda p, toks, rows, hit: model.prefill_rows(
            p, toks, 25, 64, prefix_rows=rows, prefix_len=hit))(
        params, suffix, prefix, np.int32(16))
    np.testing.assert_allclose(logits, want[24], atol=ATOL)
    for path in rows:
        np.testing.assert_allclose(again[path]["latent"][..., :25],
                                   rows[path]["latent"][..., :25], atol=1e-6)


def test_generate_runs_the_same_two_paths(served):
    model, params, seq, want = served
    out = model.generate(params, jnp.asarray(seq[None, :30]), 5)
    assert out.shape == (1, 35)
    assert int(out[0, 30]) == int(want[29].argmax())


# -- the two attention paths ---------------------------------------------------

def test_absorbed_equals_expanded_on_the_same_latent():
    attn = nn.MultiheadLatentAttention(64, 4, 24, 16, 8, 8, 8,
                                       softmax_scale=0.131)
    attn._assign_paths()
    p = attn.init(jax.random.key(0))[""]
    ks = jax.random.split(jax.random.key(1), 3)
    q_nope = jax.random.normal(ks[0], (3, 2, 4, 8))
    q_pe = jax.random.normal(ks[1], (3, 2, 4, 8))
    latent = jax.random.normal(ks[2], (3, 24, 40))
    # each row sees its own number of columns
    seen = jnp.asarray([[5, 6], [40, 40], [1, 2]])
    mask = (jnp.arange(40)[None, None, :] < seen[:, :, None])[:, None]
    a = attn._absorbed(p, q_nope, q_pe,
                       lambda q: attn._attend_latent(q, latent, mask))
    e = attn._expanded(p, q_nope, q_pe, latent, mask)
    assert a.shape == e.shape == (3, 2, 4, 8)
    np.testing.assert_allclose(a, e, atol=1e-5)
    # and neither ignores the shared key or the scale
    other = attn._expanded(p, q_nope, 0 * q_pe, latent, mask)
    assert float(jnp.abs(other - e).max()) > 1e-3


def test_which_path_a_call_takes_follows_from_its_index(served, monkeypatch):
    model, params, seq, _ = served
    taken = []
    for name in ("_absorbed", "_expanded"):
        inner = getattr(nn.MultiheadLatentAttention, name)
        monkeypatch.setattr(
            nn.MultiheadLatentAttention, name,
            lambda self, *a, _n=name, _f=inner: (taken.append(_n),
                                                 _f(self, *a))[1])
    model.apply(params, seq[None, :8])
    assert set(taken) == {"_expanded"}
    pool = model.init_slot_cache(2, 32)
    prompt = np.zeros(16, np.int32)
    _, pool, _ = model.prefill_into_slot(params, prompt, 9, 0, pool)
    assert set(taken) == {"_expanded"}
    taken.clear()
    model.decode_step(params, np.zeros(2, np.int32),
                      np.array([9, 0], np.int32), pool)
    assert set(taken) == {"_absorbed"}
    assert not model.slot_decode_kernel(pool) or jax.default_backend() == "tpu"


def test_a_whole_prompt_rebuilds_keys_for_its_bucket_alone(served):
    """A prompt from position 0 known while tracing stops at the bucket's
    columns: no (t, Tmax) scores in the prefill program."""
    model, params, _, _ = served
    pool = jax.eval_shape(lambda: model.init_slot_cache(2, 128))
    text = jax.jit(model.prefill_into_slot).lower(
        params, jnp.zeros(16, jnp.int32), 9, 0, pool).as_text()
    assert "x16x16x" in text or "16x16x" in text
    assert "16x128x" not in text


# -- a whole prompt's attention through the flash forward kernel (ISSUE 39) ----

#: heads of 192 / 128 as published, everything else reduced; a 1,024 bucket
WIDE = dict(CFG, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            num_attention_heads=2, num_hidden_layers=2,
            max_position_embeddings=1024)


@pytest.fixture(scope="module")
def wide():
    """The model at WIDE, a prompt of 900 in a 1,024 bucket, the float32
    reference's logits over it, and ``prefill_rows`` under either
    ``attention_impl`` (the kernel interpreted)."""
    model = _model(WIDE)
    params = _perturbed(model.init(jax.random.key(0)))
    prompt = np.asarray(jax.random.randint(jax.random.key(1), (1024,), 0,
                                           211), np.int32)
    want = np.asarray(REF.forward(WIDE, REF.stack_params(WIDE, params),
                                  prompt[None, :900])[0])
    got = {}
    for impl in ("dense", "flash"):
        with nn.attention_impl(impl):
            logits, rows, _ = jax.jit(lambda p, x: model.prefill_rows(
                p, x, 900, 1024))(params, prompt)
        got[impl] = np.asarray(logits), jax.tree.map(np.asarray, rows)
    return model, params, prompt, want, got


def test_a_whole_prompt_on_the_kernel_is_the_dense_branchs(wide):
    _, _, _, want, got = wide
    np.testing.assert_allclose(got["flash"][0], want[899], atol=ATOL)
    np.testing.assert_allclose(got["flash"][0], got["dense"][0], atol=ATOL)
    for path, entry in got["dense"][1].items():
        np.testing.assert_allclose(got["flash"][1][path]["latent"],
                                   entry["latent"], atol=ATOL)


def test_when_a_latent_layers_prefill_takes_the_kernel(wide, monkeypatch):
    model, params, prompt, _, _ = wide
    attn = model.block0.attn
    assert not attn.takes_prefill_kernel(1024, 0)      # the CPU's default
    with nn.attention_impl("flash"):
        assert attn.takes_prefill_kernel(1024, 0)
        assert attn.takes_prefill_kernel(4096, 0)
        assert not attn.takes_prefill_kernel(512, 0)   # a short bucket
        # a prefix hit's suffix: the position is traced, keys outnumber
        # queries
        assert not attn.takes_prefill_kernel(1024, jnp.int32(0))
        assert not attn.takes_prefill_kernel(1024, 16)
        assert not _model().block0.attn.takes_prefill_kernel(1024, 0)  # 16/8
    with nn.attention_impl("dense"):
        assert not attn.takes_prefill_kernel(1024, 0)
    # which branch a call takes, by what it shows
    taken = []
    for name in ("_expanded", "_expanded_flash"):
        inner = getattr(nn.MultiheadLatentAttention, name)
        monkeypatch.setattr(
            nn.MultiheadLatentAttention, name,
            lambda self, *a, _n=name, _f=inner: (taken.append(_n),
                                                 _f(self, *a))[1])
    with nn.attention_impl("flash"):
        jax.eval_shape(lambda: model.apply(params, prompt[None]))
        assert set(taken) == {"_expanded"}      # the plain forward: no cache
        taken.clear()
        rows = model.init_slot_cache(1, 2048)
        jax.eval_shape(lambda: model.prefill_rows(
            params, prompt, 1040, 2048, prefix_rows=rows, prefix_len=16))
        assert set(taken) == {"_expanded"}      # a prefix hit's suffix
        taken.clear()
        jax.eval_shape(lambda: model.prefill_rows(params, prompt, 900, 1024))
        assert set(taken) == {"_expanded_flash"}
        taken.clear()
        jax.eval_shape(lambda: model.prefill_rows(params, prompt[:512], 500,
                                                  1024))
        assert set(taken) == {"_expanded"}


def _squares(jaxpr, t) -> list:
    """Shapes of every array an equation of ``jaxpr`` produces, sub-programs
    (a jitted call, a kernel's body) included, whose last two axes are both
    ``t`` long or longer."""
    found = []
    for eqn in jaxpr.eqns:
        found += [v.aval.shape for v in eqn.outvars
                  if len(getattr(v.aval, "shape", ())) >= 2
                  and min(v.aval.shape[-2:]) >= t]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _squares(sub, t)
    return found


def test_the_kernel_branchs_program_holds_no_score_tensor(wide):
    """Not only absent from one trace: no equation of the prefill program
    produces a ``t x t`` array on the kernel (neither scores nor mask); the
    dense branch's produces ``heads x t x t``."""
    model, params, prompt, _, _ = wide
    seen = {}
    for impl in ("dense", "flash"):
        with nn.attention_impl(impl):
            seen[impl] = _squares(jax.make_jaxpr(
                lambda p, x: model.prefill_rows(p, x, 900, 1024))(
                    params, prompt).jaxpr, 1024)
    assert (1, WIDE["num_attention_heads"], 1024, 1024) in seen["dense"]
    assert seen["flash"] == []


def test_the_engine_serves_the_same_tokens_on_either_branch(wide):
    model, params, prompt, want, _ = wide
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
            eng.reset_stats()
            assert set(eng.stats()["prefill_attn"].values()) == {0}
    assert served["flash"][0] == served["dense"][0]
    assert served["flash"][0][0] == int(want[899].argmax())
    assert len(served["flash"][0]) == 3
    # 2 heads x 2 layers, 900 true tokens in a bucket of 1,024; what the
    # kernel executes is tile_plan's own count (float32: grid tiles of 512)
    from tpu_dist.ops.flash_attention import tile_plan
    plan = tile_plan(1024, 1024, True, dtype=jnp.float32)
    needed = 4 * (900 * 901 // 2)
    assert served["flash"][1] == {
        "prefills": 1, "kernel_prefills": 1, "pairs_needed": needed,
        "pairs_executed": 4 * plan["executed"] * 256 * 256}
    assert served["dense"][1] == {
        "prefills": 1, "kernel_prefills": 0, "pairs_needed": needed,
        "pairs_executed": 4 * 1024 * 1024}


# -- the decode kernel against the dense branch -------------------------------

TMAX = 1024
# free, first column, the 128-lane edges, the block (512) edges, the row's
# last column, and a full row (the new column is dropped)
LENGTHS = [0, 1, 127, 128, 129, 511, 512, 513, TMAX - 1, TMAX]


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def step(request):
    """One absorbed decode step of one latent-attention layer over a random
    pool through both branches (the kernel under the Pallas interpreter):
    ``(pool before, dense (out, state), kernel (out, state), tolerance)``.
    float32: summation order alone, 1e-5; bfloat16: the kernel keeps
    float32 scores and statistics where the dense branch rounds scores,
    probabilities and weighted latent to bfloat16 each, 3e-2
    (tests/test_decode_attention.py has the arithmetic)."""
    dtype = jnp.dtype(request.param)
    attn = nn.MultiheadLatentAttention(64, 4, 24, 32, 16, 16, 16)
    keys = jax.random.split(jax.random.key(7), 3)
    params = jax.tree.map(lambda a: a.astype(dtype), attn.init(keys[0]))
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    pool = attn.init_cache(len(LENGTHS), TMAX, dtype)
    pool = {"latent": jax.random.normal(keys[1], pool["latent"].shape, dtype)}
    x = jax.random.normal(keys[2], (len(LENGTHS), 1, 64), dtype)

    def run(impl):
        with nn.attention_impl(impl):
            assert attn.takes_slot_kernel(pool) == (impl == "flash")
            out, st = jax.jit(lambda p, x, s: attn.apply(p, x, state=s))(
                params, x, {attn._path: dict(pool, index=lengths)})
        return np.asarray(out, np.float32)[:, 0], st[attn._path]

    return (pool, run("dense"), run("flash"),
            1e-5 if dtype == jnp.float32 else 3e-2)


def test_the_kernels_output_is_the_dense_branchs(step):
    _, (dense, _), (kernel, _), tol = step
    busy = [b for b, n in enumerate(LENGTHS) if n > 0]
    np.testing.assert_allclose(kernel[busy], dense[busy], atol=tol, rtol=0)


def test_the_kernel_writes_the_new_column_and_nothing_else(step):
    pool, (_, dense), (_, kernel), _ = step
    before, wrote, after = (np.array(t["latent"], np.float32)
                            for t in (pool, dense, kernel))
    for b, n in enumerate(LENGTHS):
        if 0 < n < TMAX:
            np.testing.assert_array_equal(after[b, :, n], wrote[b, :, n])
            after[b, :, n] = before[b, :, n]
    # a free slot, a full row (the column at Tmax is dropped) and every
    # other column of a busy row: bit for bit the input
    np.testing.assert_array_equal(after, before)
    np.testing.assert_array_equal(np.asarray(kernel["index"]),
                                  np.asarray(LENGTHS) + 1)


def test_when_a_latent_layer_takes_the_kernel():
    attn = nn.MultiheadLatentAttention(64, 4, 24, 32, 16, 16, 16)
    ok = attn.init_cache(2, 256, jnp.bfloat16)
    assert not attn.takes_slot_kernel(ok)            # a CPU run: dense
    with nn.attention_impl("flash"):
        assert attn.takes_slot_kernel(ok)
        assert not attn.takes_slot_kernel(attn.init_cache(2, 192))
        odd = nn.MultiheadLatentAttention(64, 4, 24, 24, 16, 8, 16)
        assert odd.takes_slot_kernel(odd.init_cache(2, 256))     # float32
        assert not odd.takes_slot_kernel(odd.init_cache(2, 256,
                                                        jnp.bfloat16))
        model = _model(kv_lora_rank=32, qk_rope_head_dim=16)
        assert model.slot_decode_kernel(model.init_slot_cache(2, 128))
    with nn.attention_impl("dense"):
        assert not attn.takes_slot_kernel(ok)


# -- bucket independence and slot isolation -----------------------------------

def test_latent_columns_and_logits_do_not_depend_on_the_bucket(served):
    model, params, seq, want = served
    got = {}
    for bucket in (32, 64):
        prompt = np.full(bucket, 3, np.int32)       # padding is a real id
        prompt[:25] = seq[:25]
        logits, rows, _ = jax.jit(model.prefill_rows, static_argnums=3)(
            params, prompt, 25, 64)
        got[bucket] = (np.asarray(logits), jax.tree.map(
            lambda a: np.asarray(a[..., :25]), rows))
    np.testing.assert_allclose(got[32][0], got[64][0], atol=ATOL)
    np.testing.assert_allclose(got[32][0], want[24], atol=ATOL)
    for path, entry in got[32][1].items():
        assert set(entry) == {"latent"} and entry["latent"].shape == (1, 24,
                                                                      25)
        np.testing.assert_allclose(entry["latent"],
                                   got[64][1][path]["latent"], atol=1e-6)


def test_a_free_slots_row_leaves_every_other_slots_columns_as_they_were(
        served):
    model, params, seq, _ = served
    pool = model.init_slot_cache(3, 32)
    for slot, n in ((0, 9), (2, 14)):
        prompt = np.zeros(16, np.int32)
        prompt[:n] = seq[:n]
        _, pool, _ = model.prefill_into_slot(params, prompt, n, slot, pool)
    before = jax.tree.map(np.asarray, pool)
    lengths = np.array([9, 0, 14], np.int32)
    _, after, _ = jax.jit(model.decode_step)(
        params, np.array([4, 0, 6], np.int32), lengths, pool)
    for path, entry in jax.tree.map(np.asarray, after).items():
        was, now = before[path]["latent"], entry["latent"]
        for slot, n in ((0, 9), (2, 14)):
            np.testing.assert_array_equal(now[slot, :, :n], was[slot, :, :n])
            np.testing.assert_array_equal(now[slot, :, n + 1:],
                                          was[slot, :, n + 1:])
            assert np.abs(now[slot, :, n]).max() > 0
        # the free slot wrote (garbage) at column 0 of its own row alone
        np.testing.assert_array_equal(now[1, :, 1:], was[1, :, 1:])
    # and the busy rows' logits do not depend on who shares the pool
    alone = model.init_slot_cache(3, 32)
    prompt = np.zeros(16, np.int32)
    prompt[:9] = seq[:9]
    _, alone, _ = model.prefill_into_slot(params, prompt, 9, 0, alone)
    a, _, _ = model.decode_step(params, np.array([4, 0, 0], np.int32),
                                np.array([9, 0, 0], np.int32), alone)
    b, _, _ = model.decode_step(params, np.array([4, 0, 6], np.int32),
                                lengths, pool)
    np.testing.assert_allclose(a[0], b[0], atol=ATOL)


# -- the router -----------------------------------------------------------------

def _route(layer, p, x):
    """(indices, weights) of the picks, through the layer's own code."""
    seen = {}

    def spy(p_, xt, gate_vals, gate_idx, held, *rest):
        seen["idx"], seen["w"] = gate_idx + layer.expert_offset, gate_vals
        return jnp.zeros_like(xt)

    layer._forward_dropless = spy
    layer._assign_paths()
    layer.apply({"": p}, x)
    return np.asarray(seen["idx"]), np.asarray(seen["w"])


def _moe(**over):
    kw = dict(dim=32, num_experts=16, hidden=16, top_k=4, dispatch="dropless",
              gated=True, scoring="sigmoid", selection_bias=True,
              routed_scale=2.827)
    return nn.MoELayer(**dict(kw, **over))


def test_the_bias_chooses_and_the_unbiased_scores_weigh():
    layer = _moe()
    p = layer.init(jax.random.key(0))[""]
    x = jax.random.normal(jax.random.key(1), (64, 32))
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    flat = dict(p, router_bias=jnp.zeros(16))
    idx0, w0 = _route(layer, flat, x)
    # without a bias: the 4 largest scores, renormalised, times 2.827
    np.testing.assert_array_equal(np.sort(idx0, -1),
                                  np.sort(np.argsort(-scores, -1)[:, :4], -1))
    np.testing.assert_allclose(w0.sum(-1), 2.827, rtol=1e-6)
    # a bias large enough to force expert 11 into every token's picks
    forced = dict(p, router_bias=jnp.zeros(16).at[11].set(5.0))
    idx1, w1 = _route(layer, forced, x)
    assert (idx1 == 11).any(-1).all()
    assert (np.sort(idx1, -1) != np.sort(idx0, -1)).any()      # the SET moved
    # ... and the weights are the same formula of the UNBIASED scores
    picked = np.take_along_axis(scores, idx1, -1)
    np.testing.assert_allclose(
        w1, 2.827 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(w1.sum(-1), 2.827, rtol=1e-6)
    # the drawn bias changes some of a token's picks and not all
    idx2, _ = _route(layer, p, x)
    kept = np.array([len(set(a) & set(b)) for a, b in zip(idx0, idx2)])
    assert 0 < (kept < 4).mean() and kept.min() >= 1 and kept.mean() > 2


def test_router_forms_and_groups():
    soft = _moe(scoring="softmax", selection_bias=False, routed_scale=1.0)
    p = soft.init(jax.random.key(0))[""]
    assert "router_bias" not in p
    x = jax.random.normal(jax.random.key(1), (8, 32))
    _, w = _route(soft, p, x)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        _moe(scoring="tanh")
    with pytest.raises(NotImplementedError, match="n_group 8"):
        _model(n_group=8, topk_group=4)
    assert _model(n_group=1, topk_group=1).layer_kinds[-1] == "moe"
    greedy = _model(topk_method="greedy")
    assert "router_bias" not in jax.eval_shape(
        lambda: greedy.init(jax.random.key(0)))["block1.mlp"]


def test_the_shared_experts_gate_is_optional():
    x = jax.random.normal(jax.random.key(1), (8, 32))
    for gate in (True, False):
        layer = _moe(shared_hidden=16, shared_gate=gate)
        layer._assign_paths()
        p = layer.init(jax.random.key(0))[""]
        assert ("shared_gate" in p) == gate
        zero = dict(p, **{k: jnp.zeros_like(p[k])
                          for k in ("w1", "w2", "w3")})
        got = layer.apply({"": zero}, x)
        plain = (jax.nn.silu(x @ p["shared_w1"]) * (x @ p["shared_w3"])
                 ) @ p["shared_w2"]
        want = jax.nn.sigmoid(x @ p["shared_gate"]) * plain if gate else plain
        np.testing.assert_allclose(got, want, atol=1e-6)


# -- the share ties to the model ----------------------------------------------

def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Four chips that hold 4 of the 16 experts each: the routed parts add
    up to the uncut layer's, with the shared expert, which every chip
    computes alike, counted once; the uncut layer is the reference's."""
    whole = _moe(shared_hidden=16, shared_gate=False)
    whole._assign_paths()
    p = whole.init(jax.random.key(0))[""]
    x = jax.random.normal(jax.random.key(1), (48, 32))
    shared = (jax.nn.silu(x @ p["shared_w1"]) * (x @ p["shared_w3"])
              ) @ p["shared_w2"]
    total = jnp.zeros_like(x)
    for first in range(0, 16, 4):
        part = _moe(shared_hidden=16, shared_gate=False, experts_held=4,
                    expert_offset=first)
        part._assign_paths()
        held = dict(p, **{k: p[k][first:first + 4]
                          for k in ("w1", "w2", "w3")})
        total = total + part.apply({"": held}, x) - shared
    uncut = whole.apply({"": p}, x)
    np.testing.assert_allclose(total + shared, uncut, atol=2e-5)
    cfg = dict(num_experts_per_tok=4, norm_topk_prob=True,
               routed_scaling_factor=2.827)
    ref = REF.moe_routed(cfg, p, x) + REF.gated_mlp(
        p["shared_w1"], p["shared_w3"], p["shared_w2"], x)
    np.testing.assert_allclose(uncut, ref, atol=2e-5)


@pytest.mark.parametrize("sent,rows_over", [
    ("a_usual_share", 0), ("one_hot_expert", 192), ("every_pick_held", 768)])
def test_a_small_shares_row_buffer_grows_by_fours(sent, rows_over):
    """A layer that holds 4 of 128 experts sizes its row buffer for twice
    its share (192 rows of 2,048 picks); the buffer of every pick is eleven
    times that, so one of four times the rows (768) stands between them:
    a prompt whose every token picks one held expert takes it, one whose
    every token picks all four takes the last, and each is the reference's
    given the same share, no row dropped."""
    layer = _moe(num_experts=128, top_k=8, experts_held=4)
    p = dict(layer.init(jax.random.key(3))[""])
    x = jax.random.normal(jax.random.key(4), (256, 32))
    # one input dimension held at 1, read by chosen router columns alone:
    # their logits stand ~8 over the others' (a sigmoid short of 1)
    x = x.at[:, 0].set(1.0)
    p["router"] = p["router"].at[0].set(0.0)
    if sent == "one_hot_expert":
        p["router"] = p["router"].at[0, 2].set(8.0)
    elif sent == "every_pick_held":
        p["router"] = p["router"].at[0, :4].set(8.0)
    state = {"": dict(layer.init_counters(), valid=jnp.ones(256, bool))}
    with jax.default_matmul_precision("highest"):
        out, new = jax.jit(lambda p, x: layer.apply({"": p}, x, state=state))(
            p, x)
        cfg = dict(num_experts_per_tok=8, norm_topk_prob=True,
                   routed_scaling_factor=2.827)
        want = REF.moe_routed(cfg, p, x)
    np.testing.assert_allclose(out, want, rtol=0, atol=2e-5)
    c = jax.tree.map(np.asarray, new[""])
    assert layer._block_rows(2048, jnp.float32) == 16
    assert c["computed_rows"] == (-(-c["rows"][:4] // 16) * 16).sum()
    assert c["computed_rows"] > rows_over
    if sent != "every_pick_held":
        assert c["computed_rows"] <= (768 if rows_over else 192)
    else:
        assert c["held_rows"] == 4 * 256


# -- YaRN -----------------------------------------------------------------------

def test_yarn_frequencies_and_the_softmax_scale_by_hand():
    """Kimi K2's rope_scaling: 64 rotary dims, theta 50,000, factor 32,
    original context 4,096, both betas 1.  The pair that turns once within
    4,096 positions is 64 ln(4096 / 2 pi) / (2 ln 50000) = 19.17: pairs 0-19
    keep their frequency, pairs 20-31 take a 32nd of it, no blend between
    (the range's ends are 19 and 20)."""
    turns = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000))
    assert 19.1 < turns < 19.2
    f = nn.yarn_inv_freq(64, 50000, 32, 4096, 1, 1)
    own = 50000.0 ** (-np.arange(32) / 32.0)
    assert f.shape == (32,) and f.dtype == np.float32
    np.testing.assert_allclose(f[:20], own[:20], rtol=1e-6)
    np.testing.assert_allclose(f[20:], own[20:] / 32, rtol=1e-6)
    np.testing.assert_allclose(f[19], 1.6217599e-03, rtol=1e-6)
    np.testing.assert_allclose(f[20], 3.6140468e-05, rtol=1e-6)
    # the reference computes them apart and agrees
    real = dict(CFG, qk_rope_head_dim=64, rope_scaling=dict(
        CFG["rope_scaling"], original_max_position_embeddings=4096))
    np.testing.assert_allclose(f, REF.yarn_inv_freq(real), rtol=1e-6)
    # a range whose ends coincide is widened by the published 0.001
    # (theta 4096 / 2 pi, dim 8: the pair that turns once is pair 4 exactly)
    g = nn.yarn_inv_freq(8, 4096 / (2 * math.pi), 4, 4096, 1, 1)
    base = (4096 / (2 * math.pi)) ** (-np.arange(4) / 4.0)
    np.testing.assert_allclose(g, base, rtol=1e-6)      # pairs 0-3 < low = 4
    # a blend in between: betas 32 and 1, the published DeepSeek-V3 values
    h = nn.yarn_inv_freq(64, 10000, 40, 4096, 32, 1)
    assert h[0] == 1.0 and np.all(np.diff(h) < 0)
    np.testing.assert_allclose(h[-1], 10000.0 ** (-31 / 32) / 40, rtol=1e-6)
    # the softmax scale: 192^-1/2 (0.1 ln 32 + 1)^2
    assert nn.yarn_mscale(32, 1) == pytest.approx(1.3465736)
    assert nn.yarn_mscale(1, 1) == 1.0 and nn.yarn_mscale(32, 0) == 1.0
    big = _model(qk_nope_head_dim=128, qk_rope_head_dim=64,
                 rope_scaling_original_max_position_embeddings=4096)
    attn = big.block0.attn
    assert attn.softmax_scale == pytest.approx(0.13086080, rel=1e-6)
    np.testing.assert_allclose(attn.rope_inv_freq, f)
    with pytest.raises(NotImplementedError, match="mscale"):
        _model(rope_scaling_mscale_all_dim=0.5)


def test_rope_with_given_frequencies_is_rope():
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 8))
    pos = jnp.arange(5)
    own = 10000.0 ** (-np.arange(4) / 4.0)
    np.testing.assert_allclose(nn.rotary_embed(x, pos, inv_freq=own),
                               nn.rotary_embed(x, pos), atol=1e-6)
    slow = nn.rotary_embed(x, 4 * pos, inv_freq=own / 4)
    np.testing.assert_allclose(slow, nn.rotary_embed(x, pos), atol=1e-5)


# -- nn/cache.py on a latent-only cache ----------------------------------------

def _latent_rows(length, seed=0, layers=3, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return {f"block{i}.attn": {"latent": rng.standard_normal(
        (1, 24, length)).astype(dtype)} for i in range(layers)}


def test_the_cache_functions_on_a_latent_only_pool():
    model = _model()
    pool = model.init_slot_cache(3, 32, jnp.bfloat16)
    assert set(pool) == {f"block{i}.attn" for i in range(3)}
    assert all(set(e) == {"latent"} and e["latent"].shape == (3, 24, 32)
               for e in pool.values())
    assert nn.cache.is_timed("latent") and nn.cache.state_leaves(pool) == []
    assert nn.cache.kv_entries(pool) == list(pool.values())
    assert nn.cache.pool_leaf(pool["block0.attn"]) is pool["block0.attn"][
        "latent"]
    assert nn.cache.pool_leaf({"state": 1, "conv": 2}) is None
    k = jnp.zeros((1, 2, 4, 8))
    assert nn.cache.pool_leaf({"k_scale": 0, "v": 1, "k": k}) is k
    assert nn.cache.extent(pool) == (32, jnp.bfloat16)
    # a position holds 24 bfloat16 numbers in each of 3 layers, no state
    assert nn.cache.slot_bytes(pool) == (0, 3 * 24 * 2)
    assert nn.cache.token_template(pool)["block1.attn"] == {
        "latent": ((24,), np.dtype(jnp.bfloat16))}
    nn.cache.require_timed(pool, "a mover")
    rows = _latent_rows(10)
    padded = nn.cache.pad_time(rows, 32)
    for path, entry in padded.items():
        assert entry["latent"].shape == (1, 24, 32)
        np.testing.assert_array_equal(entry["latent"][..., :10],
                                      rows[path]["latent"])
        assert not entry["latent"][..., 10:].any()
    joined = nn.cache.join_time([
        jax.tree.map(lambda a: nn.cache.time_slice(a, lo, hi), rows)
        for lo, hi in ((0, 4), (4, 10))])
    np.testing.assert_array_equal(joined["block2.attn"]["latent"],
                                  rows["block2.attn"]["latent"])
    # the slot write lands a bucket's columns at column 0 of one slot
    filled = jax.tree.map(lambda a: jnp.ones_like(a, jnp.float32), pool)
    wrote = nn.cache.write_slot_rows(filled, padded, 1)
    for path, entry in wrote.items():
        got = np.asarray(entry["latent"])
        np.testing.assert_array_equal(got[1], padded[path]["latent"][0])
        assert (got[[0, 2]] == 1).all()
    # call and split round-trip it
    state = nn.cache.call_state(pool, jnp.asarray([0, 3, 9]))
    back, counters = nn.cache.split_state(state)
    assert counters == {} and jax.tree.structure(back) == jax.tree.structure(
        pool)


def test_the_prefix_cache_round_trips_latent_rows():
    pc = serve.PrefixCache(block_tokens=4)
    prompt = np.arange(10, 26, dtype=np.int32)
    rows = _latent_rows(16, seed=1)
    assert pc.insert(prompt, rows, 16) == 4
    hit, got = pc.match(np.concatenate([prompt, [7, 8, 9]]))
    assert hit == 16
    for path in rows:
        np.testing.assert_array_equal(got[path]["latent"],
                                      rows[path]["latent"])
    hit, got = pc.match(prompt)
    assert hit == 12 and got["block0.attn"]["latent"].shape == (1, 24, 12)


def test_kv_transfer_round_trips_latent_rows():
    from tpu_dist.collectives.transport import DataPlane
    from tpu_dist.dist.store import TCPStore
    store = TCPStore(is_master=True)
    dp0, dp1 = DataPlane(store, 0, 2), DataPlane(store, 1, 2)
    try:
        template = serve.kv_template(_model().init_slot_cache(1, 16))
        kv0, kv1 = (serve.KVTransfer(dp, template) for dp in (dp0, dp1))
        rows, err = _latent_rows(12, seed=3), []

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
        for path in rows:
            np.testing.assert_array_equal(got["rows"][path]["latent"],
                                          rows[path]["latent"][..., :10])
    finally:
        dp0.close(), dp1.close()
        store.close()


def test_the_disaggregated_engine_lands_latent_rows_in_a_slot():
    from tpu_dist.serve.disagg import DisaggSlotEngine
    model = _model()
    eng = DisaggSlotEngine(model, model.init(jax.random.key(0)), kv=None,
                           dispatch_ch=None, arrive_ch=None, num_slots=2,
                           max_len=32, rank=0)
    rows = nn.cache.pad_time(_latent_rows(10, seed=5), 16)
    eng.cache = eng._inject(eng.cache, rows, np.int32(1))
    for path, entry in eng.cache.items():
        got = np.asarray(entry["latent"])
        np.testing.assert_array_equal(got[1, :, :16], rows[path]["latent"][0])
        assert not got[0].any()


def test_sharded_serving_refuses_a_latent_leaf_by_name():
    dense = _model(first_k_dense_replace=3)      # no expert layer to refuse
    with pytest.raises(NotImplementedError,
                       match=r"block0\.attn\.latent.*no head axis"):
        serve.ShardedLM(dense, 0, 2)
    nn.cache.require_heads(TransformerLM(97, dim=32, depth=1, num_heads=2
                                         ).init_slot_cache(1, 8), "anyone")


# -- the engine ----------------------------------------------------------------

def test_decode_need_against_a_hand_count_on_a_two_slot_pool():
    """float32 parameters.  A step reads every leaf but the token table and
    the routed experts; an expert is 3 x 64 x 32 numbers; a position holds
    24 numbers in each of 3 layers; attention costs 2 x 4 x (24 + 16)
    operations a resident position a layer."""
    model = _model(SHARE)
    params = model.init(jax.random.key(0))
    eng = serve.SlotEngine(model, params, num_slots=2, max_len=64,
                           min_bucket=16)
    size = lambda tree: sum(int(a.size) for a in jax.tree.leaves(tree))
    experts = sum(size({k: params[f"block{i}.mlp"][k]
                        for k in ("w1", "w2", "w3")}) for i in (1, 2))
    assert experts == 2 * 4 * 3 * 64 * 32
    fixed = size(params) - 211 * 64 - experts
    need = eng._need
    assert need["fixed_params"] == fixed and need["fixed_bytes"] == 4 * fixed
    assert need["experts"] == {f"block{i}.mlp": (4 * 3 * 64 * 32, 3 * 64 * 32)
                               for i in (1, 2)}
    assert need["attend_flops"] == 3 * 2 * 4 * (24 + 16)
    assert eng.stats()["decode_need"] == dict(
        steps=0, rows=0, positions=0, weight_bytes=0, cache_bytes=0, flops=0)

    eng.admit(serve.Request(np.arange(1, 10), max_new_tokens=4))    # 9 long
    eng.admit(serve.Request(np.arange(1, 21), max_new_tokens=3))    # 20 long
    eng.reset_stats()
    eng.step()          # 2 busy rows over 9 and 20 resident positions
    eng.step()          # 10 and 21
    eng.step()          # one row, 11 (the other request ended)
    st = eng.stats()
    got, moe = st["decode_need"], st["moe"]["by_phase"]["decode"]
    positions = (9 + 1 + 20 + 1) + (10 + 1 + 21 + 1) + (11 + 1)
    assert got["steps"] == 3 and got["rows"] == 5
    assert got["positions"] == positions
    assert got["cache_bytes"] == 3 * 24 * 4 * positions
    assert got["cache_bytes"] == st["state"]["kv_bytes"]
    assert got["weight_bytes"] == (3 * 4 * fixed
                                   + moe["experts_hit"] * 4 * 3 * 64 * 32)
    assert got["flops"] == (2 * (fixed * 5 + moe["held_rows"] * 3 * 64 * 32)
                            + 3 * 2 * 4 * 40 * positions)
    assert 0 < moe["experts_hit"] <= 3 * 2 * 4 and moe["held_rows"] <= 5 * 8
    # the latent pool reports what a K/V pool reports
    attn = st["decode_attn"]
    assert attn["steps"] == 3 and attn["kernel"] is False
    assert attn["kv_blocks_read"] == 5 and attn["kv_blocks_pool"] == 3 * 2
    eng.reset_stats()
    assert eng.stats()["decode_need"] == dict(
        steps=0, rows=0, positions=0, weight_bytes=0, cache_bytes=0, flops=0)


def test_every_model_gets_the_counter():
    model = TransformerLM(97, dim=32, depth=2, num_heads=2, max_seq_len=32)
    params = model.init(jax.random.key(0))
    eng = serve.SlotEngine(model, params, num_slots=2, max_len=32)
    eng.admit(serve.Request([1, 2, 3], max_new_tokens=3))
    eng.reset_stats()
    eng.step()
    got = eng.stats()["decode_need"]
    size = sum(int(a.size) for a in jax.tree.leaves(params))
    fixed = size - 97 * 32 - 32 * 32          # less the two gathered tables
    assert got["weight_bytes"] == 4 * fixed and got["rows"] == 1
    assert got["cache_bytes"] == 2 * 2 * 32 * 4 * 4
    assert got["flops"] == 2 * fixed + 2 * (2 * 2 * 2 * 16) * 4


def test_the_engine_serves_the_model_without_a_branch(served):
    """SlotEngine end to end, two requests sharing the pool: each one's
    tokens are the greedy tokens of the reference's logits."""
    model, params, seq, want = served
    eng = serve.SlotEngine(model, params, num_slots=2, max_len=64,
                           min_bucket=16)
    assert eng.stats()["params"]["placed_leaves"] == 0
    assert nn.cache.state_leaves(eng.cache) == []
    got = []
    req = serve.Request(seq[:30], max_new_tokens=1,
                        on_token=lambda r, t: got.append(t))
    eng.admit(req)
    assert got == [int(want[29].argmax())]


# -- the models the benchmark already serves ----------------------------------

def _served_logits(model, seed=0):
    """Two prompts prefilled into slots 2 and 0 (buckets 32 and 16), then
    three decode steps: the float32 logits' bytes, hashed.  The prefill's
    rows are built at the POOL's extent, as ``prefill_into_slot`` built
    them when the digests were taken (since PR 48 it builds them at the
    bucket's, which sums a softmax over fewer masked columns: the last
    bits of float32): the digests hold the models' forward, not that
    choice."""
    params = model.init(jax.random.key(seed))
    pool, moe = model.init_slot_cache(3, 64), model.init_moe_counters()

    def prefill(params, prompt, n, slot, pool, moe):
        logits, rows, moe = model.prefill_rows(
            params, prompt, n, *nn.cache.extent(pool), counters=moe)
        return logits, nn.cache.write_slot_rows(pool, rows, slot), moe

    pre, step = jax.jit(prefill), jax.jit(model.decode_step)
    rng = np.random.default_rng(seed)
    lengths, out = np.zeros(3, np.int32), []
    for slot, n, bucket in ((2, 21, 32), (0, 9, 16)):
        prompt = np.zeros(bucket, np.int32)
        prompt[:n] = rng.integers(0, 211, n)
        lg, pool, moe = pre(params, prompt, n, slot, pool, moe)
        out.append(np.asarray(lg))
        lengths[slot] = n
    toks = np.array([5, 0, 7], np.int32)
    for _ in range(3):
        lg, pool, moe = step(params, toks, lengths, pool, moe)
        lg = np.asarray(lg)
        out.append(lg[[0, 2]])
        toks = np.where(lengths > 0, lg.argmax(-1), 0).astype(np.int32)
        lengths += lengths > 0
    return hashlib.sha256(b"".join(
        np.ascontiguousarray(a, np.float32).tobytes() for a in out)
    ).hexdigest()


def _small_model(name):
    if name == "gpt2":
        return TransformerLM(vocab_size=211, dim=64, depth=2, num_heads=4,
                             max_seq_len=128)
    if name == "kimik2":
        return _model(SHARE)
    if name == "olmoe":
        return TransformerLM(
            vocab_size=211, dim=64, depth=2, num_heads=4, max_seq_len=128,
            num_experts=8, moe_top_k=2, moe_hidden=32,
            moe_normalize_gates=False, norm_eps=1e-5, rope_theta=10000,
            norm="rmsnorm", rope=True, qk_norm=True, attn_bias=False,
            moe_gated=True, moe_dispatch="dropless")
    return Qwen3NextLM(
        vocab_size=211, dim=64, depth=4, num_heads=4, num_kv_heads=1,
        head_dim=32, full_attention_interval=4,
        partial_rotary_factor=0.25, rope_theta=10000000, norm_eps=1e-6,
        linear_key_heads=2, linear_value_heads=4, linear_key_dim=16,
        linear_value_dim=16, linear_conv_kernel=4, num_experts=16,
        experts_held=4, expert_offset=4, moe_top_k=4, moe_hidden=32,
        shared_hidden=32, moe_normalize_gates=True, max_seq_len=256)


@pytest.mark.parametrize("name,digest", [
    ("olmoe",
     "bd18a4f2fe4c9a24d1da8437edc23077feaa12c293f2020bbbf8354d65627f88"),
    ("qwen3next",
     "af678d18815f000f52c4c80c3cd7cc746f2bc2cd70b8a1435e89f5c5efa0744b"),
    ("gpt2",
     "c0e1b8382010846436091e694dc7c7c47621d6425b829d37ea187409f3c63edb"),
    ("kimik2",
     "c943e4ead157c2619aef51facacc0ede33cccffe7563ebeb38a1411eae8f0560")])
def test_the_routed_models_serve_the_parents_logits_bit_for_bit(name, digest):
    """The router's second scoring form, its selection bias and the shared
    expert's optional gate (PR 32), and the way a sublayer's output joins
    the residual (PR 38: ``TransformerBlock(residual=...)``,
    ``TransformerLM.forward``'s open and close), change nothing for the
    models that do not ask for them: the digests are the PARENT commits'
    (olmoe and qwen3next ed68261 and again 102ef64, gpt2 and kimik2
    102ef64), taken on this CPU by the same function."""
    assert _served_logits(_small_model(name)) == digest


def _trained(steps=2, seed=0):
    """``steps`` ``train_step``s of a small GPT-2 through
    ``DistributedDataParallel`` on one device (AdamW, float32): the bytes of
    every loss and of the updated parameters, hashed."""
    import tpu_dist.dist as dist
    from tpu_dist import optim
    from tpu_dist.parallel import DistributedDataParallel

    if dist.is_initialized():
        dist.destroy_process_group()
    pg = dist.init_process_group()
    try:
        ddp = DistributedDataParallel(
            TransformerLM(vocab_size=211, dim=64, depth=2, num_heads=4,
                          max_seq_len=32),
            optimizer=optim.AdamW(lr=3e-4), loss_fn=nn.CrossEntropyLoss(),
            group=dist.new_group(ranks=[0]))
        state = ddp.init(seed=seed)
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(steps):
            x = jnp.asarray(rng.integers(0, 211, (4, 32)), jnp.int32)
            y = jnp.asarray(rng.integers(0, 211, (4, 32)), jnp.int32)
            state, metrics = ddp.train_step(state, x, y)
            out.append(np.asarray(metrics["loss"], np.float32))
        params = jax.device_get(state.params)
        out += [np.asarray(params[path][name], np.float32)
                for path in sorted(params) for name in sorted(params[path])]
        return hashlib.sha256(b"".join(
            np.ascontiguousarray(a).tobytes() for a in out)).hexdigest()
    finally:
        dist.destroy_process_group()


def test_gpt2_trains_to_the_parents_loss_and_parameters_bit_for_bit():
    """``TransformerBlock.forward`` and ``TransformerLM.forward`` are the
    training cells' too: two steps' losses and parameters are the PARENT
    commit's (102ef64), taken on this CPU by the same function."""
    assert _trained() == (
        "5a9859aed9571a3bc9302be85bbe9e4703cda745688ec64c1a1662658280099b")

"""GSPMD tensor + data parallelism == single-device step (the TP oracle)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dist import nn, optim
from tpu_dist.models import TransformerLM
from tpu_dist.parallel.gspmd import (PartitionRules, TRANSFORMER_TP_RULES,
                                     make_gspmd_train_step, shard_pytree)
from tpu_dist.parallel.rules import DEFAULT_RULES, partition_pairs

# a table that binds every logical axis to None: the same program on the
# same mesh, every parameter whole on every device
REPLICATE_RULES = PartitionRules(
    partition_pairs({axis: None for axis in DEFAULT_RULES}))


@pytest.fixture(scope="module")
def mesh2d():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(np.array(devs[:8]).reshape(2, 4), ("data", "model"))


def _lm_and_batch(vocab=64, dim=32, t=16, b=4):
    model = TransformerLM(vocab_size=vocab, dim=dim, depth=2, num_heads=4,
                          max_seq_len=t)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, vocab, (b, t)))
    y = jnp.asarray(rng.integers(0, vocab, (b, t)))
    return model, x, y


def _lm_loss(vocab):
    ce = nn.CrossEntropyLoss()

    def loss_fn(logits, y):
        return ce(logits.reshape(-1, vocab), y.reshape(-1))
    return loss_fn


class TestPartitionRules:
    def test_first_match_and_default(self):
        rules = PartitionRules([(r"weight", P("model")), (r".*", P("data"))])
        assert rules.spec_for("['a']['weight']") == P("model")
        assert rules.spec_for("['a']['bias']") == P("data")
        assert PartitionRules([]).spec_for("anything") == P()

    def test_transformer_rules_cover_attention(self):
        model, _, _ = _lm_and_batch()
        params = model.init(jax.random.key(0))
        specs = TRANSFORMER_TP_RULES.tree_specs(params)
        assert specs["block0.attn"]["qkv_weight"] == P(None, "model")
        assert specs["block0.attn"]["out_weight"] == P("model", None)
        assert specs["block0.mlp.0"]["weight"] == P(None, "model")
        assert specs["block0.mlp.2"]["weight"] == P("model", None)
        assert specs["ln_f"]["weight"] == P()  # layernorm replicated


class TestGspmdStep:
    @pytest.mark.parametrize("rules", [TRANSFORMER_TP_RULES, REPLICATE_RULES],
                             ids=["tp_rules", "all_none_table"])
    def test_tp_dp_matches_single_device(self, mesh2d, rules):
        vocab = 64
        model, x, y = _lm_and_batch(vocab=vocab)
        params = model.init(jax.random.key(0))
        # the attention biases start at zero: give the row-parallel one a
        # value, so that adding it once a shard would show in the loss
        bias = params["block0.attn"]["out_bias"]
        params["block0.attn"]["out_bias"] = bias + jnp.linspace(
            -0.5, 0.5, bias.size)
        opt = optim.SGD(lr=0.1, momentum=0.9)
        opt_state = opt.init(params)
        loss_fn = _lm_loss(vocab)

        # single-device reference
        ref_step = make_gspmd_train_step(model, loss_fn, opt, donate=False)
        rp, ro, rm = ref_step(params, opt_state, x, y)

        # sharded: params per TP rules, momentum mirrors params, batch on data
        sp = shard_pytree(params, mesh2d, rules)
        so = {"momentum": shard_pytree(opt_state["momentum"], mesh2d, rules)}
        sharded = {jax.tree_util.keystr(path) for path, leaf
                   in jax.tree_util.tree_leaves_with_path(sp)
                   if leaf.sharding.spec != P()}
        if rules is REPLICATE_RULES:
            assert not sharded
        else:
            assert "['block0.attn']['qkv_weight']" in sharded
        bsh = NamedSharding(mesh2d, P("data", None))
        sx, sy = jax.device_put(x, bsh), jax.device_put(y, bsh)
        step = make_gspmd_train_step(model, loss_fn, opt, donate=False)
        np_, no, nm = step(sp, so, sx, sy)

        np.testing.assert_allclose(float(nm["loss"]), float(rm["loss"]),
                                   rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5), np_, rp)

    def test_params_actually_sharded(self, mesh2d):
        model, _, _ = _lm_and_batch()
        params = model.init(jax.random.key(0))
        sp = shard_pytree(params, mesh2d, TRANSFORMER_TP_RULES)
        qkv = sp["block0.attn"]["qkv_weight"]
        # column-sharded over 4 'model' devices → each holds 1/4 of columns
        assert qkv.sharding.spec == P(None, "model")
        shard_shape = qkv.sharding.shard_shape(qkv.shape)
        assert shard_shape[1] == qkv.shape[1] // 4

    def test_training_progresses_sharded(self, mesh2d):
        vocab = 32
        model, x, y = _lm_and_batch(vocab=vocab, b=4, t=16)
        loss_fn = _lm_loss(vocab)
        opt = optim.SGD(lr=0.5)
        params = shard_pytree(model.init(jax.random.key(0)), mesh2d,
                              TRANSFORMER_TP_RULES)
        opt_state = opt.init(params)
        bsh = NamedSharding(mesh2d, P("data", None))
        x, y = jax.device_put(x, bsh), jax.device_put(y, bsh)
        step = make_gspmd_train_step(model, loss_fn, opt)
        first = None
        for _ in range(20):
            params, opt_state, m = step(params, opt_state, x, y)
            first = first if first is not None else float(m["loss"])
        assert float(m["loss"]) < first


class TestTensorParallelGenerate:
    """Distributed serving via shardings alone: jit the WHOLE KV-cache
    decode loop (prefill + lax.scan of single-token steps) with the params
    Megatron-sharded over 'model' and the prompt batch sharded over 'data'
    — the GSPMD partitioner propagates shardings into the cache created
    inside the traced generate(), inserting the per-step collectives, and
    greedy tokens must equal the single-device decode exactly."""

    def test_tp_generate_matches_single_device(self, mesh2d):
        from tpu_dist.nn.attention import attention_impl

        vocab = 64
        model = TransformerLM(vocab_size=vocab, dim=32, depth=2,
                              num_heads=4, max_seq_len=32)
        params = model.init(jax.random.key(0))
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, vocab, (4, 8)))
        ref = model.generate(params, prompt, max_new_tokens=8)

        sp = shard_pytree(params, mesh2d, TRANSFORMER_TP_RULES)
        assert sp["block0.attn"]["qkv_weight"].sharding.spec \
            == P(None, "model")
        sprompt = jax.device_put(
            prompt, NamedSharding(mesh2d, P("data", None)))
        with attention_impl("dense"):  # Pallas custom calls can't be cut
            out = jax.jit(lambda p, t: model.generate(p, t, 8))(sp, sprompt)
            # composes with the quantized KV cache: still token-exact
            out_i8 = jax.jit(lambda p, t: model.generate(
                p, t, 8, cache_dtype=jnp.int8))(sp, sprompt)
        if jax.devices()[0].platform == "cpu":
            # the virtual CPU mesh reduces deterministically, so greedy
            # tokens are bit-exact vs the single-device decode
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
            np.testing.assert_array_equal(np.asarray(out_i8),
                                          np.asarray(ref))
        else:
            # real-chip collectives may reorder reductions; a greedy
            # near-tie could flip a token and cascade, so demand logits
            # agreement plus a weaker decode-output contract (shape,
            # vocab range, prompt passthrough, majority token agreement)
            # instead of bit-exact tokens
            lg_tp = jax.jit(model.apply)(sp, sprompt)
            lg_ref = model.apply(jax.device_get(sp), prompt)
            np.testing.assert_allclose(np.asarray(lg_tp),
                                       np.asarray(lg_ref),
                                       rtol=2e-2, atol=2e-2)
            tp_len = prompt.shape[1]
            for o in (np.asarray(out), np.asarray(out_i8)):
                assert o.shape == np.asarray(ref).shape
                assert ((o >= 0) & (o < vocab)).all()
                np.testing.assert_array_equal(o[:, :tp_len],
                                              np.asarray(prompt))
                # agreement over the GENERATED region only (the prompt
                # passthrough is already pinned above): garbage decode
                # agrees at ~1/vocab, while a single legitimate near-tie
                # flip mid-sequence still leaves the prefix agreeing
                agree = (o[:, tp_len:]
                         == np.asarray(ref)[:, tp_len:]).mean()
                assert agree >= 0.25, f"decode diverged: {agree:.2f} agree"


class TestViTTensorParallel:
    """TRANSFORMER_TP_RULES applies unchanged to the ViT encoder (same
    block paths: attn qkv/out, mlp.0/mlp.2, head) — tensor-parallel
    vision with zero extra rules."""

    def test_vit_tp_matches_single_device(self, mesh2d):
        from tpu_dist.models import VisionTransformer

        model = VisionTransformer(image_size=16, patch_size=8, num_layers=2,
                                  num_heads=4, hidden_dim=32, num_classes=8)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(4, 16, 16, 3)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, 8, 4))
        # zero-init head gives zero gradients through it at step 1 only
        # for the head itself; use a non-zero-init copy so the step moves
        params = model.init(jax.random.key(0))
        params["head"]["weight"] = jnp.asarray(
            rng.normal(size=params["head"]["weight"].shape) * 0.02,
            jnp.float32)
        opt = optim.SGD(lr=0.1)
        opt_state = opt.init(params)
        ce = nn.CrossEntropyLoss()
        loss_fn = lambda logits, yy: ce(logits, yy)

        step = make_gspmd_train_step(model, loss_fn, opt, donate=False)
        rp, ro, rm = step(params, opt_state, x, y)

        sp = shard_pytree(params, mesh2d, TRANSFORMER_TP_RULES)
        so = {"momentum": shard_pytree(opt_state.get("momentum"), mesh2d,
                                       TRANSFORMER_TP_RULES)} \
            if "momentum" in opt_state else opt_state
        bsh = NamedSharding(mesh2d, P("data", None, None, None))
        sx = jax.device_put(x, bsh)
        sy = jax.device_put(y, NamedSharding(mesh2d, P("data")))
        np_, no, nm = step(sp, so, sx, sy)

        np.testing.assert_allclose(float(nm["loss"]), float(rm["loss"]),
                                   rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5), np_, rp)
        # the qkv weight really is column-sharded over 'model'
        assert sp["block0.attn"]["qkv_weight"].sharding.spec \
            == P(None, "model")

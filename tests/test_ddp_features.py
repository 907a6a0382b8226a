"""DDP wrapper extensions: grad accumulation, the sharded weight update
(ZeRO-1), mixed precision — each checked against the numerics of the plain
DDP step with whole updates (``shard_optimizer=False``: over a group the
default shards ConvNet's one large leaf, ``conv3.weight``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import tpu_dist.dist as dist
from tpu_dist import nn, optim
from tpu_dist.models import ConvNet
from tpu_dist.parallel import DDP
# compile-heavy file: excluded from the fast tier (`pytest -m "not slow"`)
pytestmark = pytest.mark.slow



@pytest.fixture
def pg():
    if dist.is_initialized():
        dist.destroy_process_group()
    pg = dist.init_process_group()
    yield pg
    if dist.is_initialized():
        dist.destroy_process_group()


def _batch(n=64, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(n, 28, 28, 1)).astype(np.float32)),
            jnp.asarray(rng.integers(0, 10, n)))


def _mk(pg, **kw):
    return DDP(ConvNet(), optimizer=optim.SGD(lr=0.05, momentum=0.9),
               loss_fn=nn.CrossEntropyLoss(), group=pg, donate=False, **kw)


def _plain(pg, **kw):
    """The oracle: every update whole and replicated."""
    return _mk(pg, shard_optimizer=False, **kw)


class TestGradAccumulation:
    def test_accum_matches_plain(self, pg):
        """k microbatches of B/k == one batch of B (same grads for
        mean-reduced loss)."""
        x, y = _batch(64)
        plain = _plain(pg)
        s0 = plain.init(seed=0)
        s1, m1 = plain.train_step(s0, x, y)

        accum = _plain(pg, accum_steps=4)
        a0 = accum.init(seed=0)
        a1, m2 = accum.train_step(a0, x, y)

        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        assert int(m1["correct"]) == int(m2["correct"])
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
            s1.params, a1.params)

    def test_bad_accum_raises(self, pg):
        with pytest.raises(ValueError, match="accum_steps"):
            _mk(pg, accum_steps=0)


class TestZero1:
    def test_matches_plain_over_steps(self, pg):
        x, y = _batch(64)
        plain = _plain(pg)
        z1 = _mk(pg, shard_optimizer=True)
        sp, sz = plain.init(seed=0), z1.init(seed=0)
        for _ in range(3):
            sp, mp = plain.train_step(sp, x, y)
            sz, mz = z1.train_step(sz, x, y)
        np.testing.assert_allclose(float(mp["loss"]), float(mz["loss"]),
                                   rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6),
            sp.params, sz.params)

    def test_opt_state_is_sharded(self, pg):
        z1 = _mk(pg, shard_optimizer=True)
        s = z1.init(seed=0)
        # the moments are shaped as their parameters; the one leaf past
        # ddp.SHARD_MIN_ELEMENTS, (3, 3, 64, 128), is held 1/8 a device
        # along the first axis 8 divides (the parameter itself too), the
        # rest replicate
        mom = s.opt_state["momentum"]["conv3"]["weight"]
        assert mom.sharding.spec == P(None, None, pg.axis_name)
        assert mom.sharding.shard_shape(mom.shape) == (3, 3, 64 // 8, 128)
        assert s.params["conv3"]["weight"].sharding.spec == mom.sharding.spec
        assert s.opt_state["momentum"]["fc1"]["weight"].sharding.spec == P()
        # stays sharded after a step
        x, y = _batch(16)
        s2, _ = z1.train_step(s, x, y)
        assert s2.opt_state["momentum"]["conv3"]["weight"].sharding.spec \
            == P(None, None, pg.axis_name)

    def test_zero1_scalar_opt_state_leaves(self, pg):
        """Optimizers with scalar step counters (AdamW, scheduled-lr SGD)
        under ZeRO-1: scalars replicate, rank>=1 leaves shard 1/world."""
        x, y = _batch(32)
        for opt in (optim.AdamW(lr=1e-3),
                    optim.SGD(lr=optim.step_lr(0.05, step_size=2),
                              momentum=0.9)):
            plain = DDP(ConvNet(), optimizer=opt,
                        loss_fn=nn.CrossEntropyLoss(), group=pg,
                        donate=False, shard_optimizer=False)
            z1 = DDP(ConvNet(), optimizer=opt,
                     loss_fn=nn.CrossEntropyLoss(), group=pg, donate=False,
                     shard_optimizer=True)
            sp, sz = plain.init(seed=0), z1.init(seed=0)
            assert sz.opt_state["step"].sharding.spec == P()
            for _ in range(3):
                sp, _ = plain.train_step(sp, x, y)
                sz, _ = z1.train_step(sz, x, y)
            assert int(sz.opt_state["step"]) == 3
            jax.tree.map(lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-6),
                sp.params, sz.params)

    def test_zero1_with_accum(self, pg):
        x, y = _batch(64)
        plain = _plain(pg)
        combo = _mk(pg, shard_optimizer=True, accum_steps=2)
        sp, sc = plain.init(seed=0), combo.init(seed=0)
        sp, _ = plain.train_step(sp, x, y)
        sc, _ = combo.train_step(sc, x, y)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6),
            sp.params, sc.params)


class TestMixedPrecision:
    def test_bf16_trains_params_stay_f32(self, pg):
        ddp = _mk(pg, compute_dtype=jnp.bfloat16)
        state = ddp.init(seed=0)
        x, y = _batch(64)
        first = None
        for _ in range(10):
            state, m = ddp.train_step(state, x, y)
            first = first if first is not None else float(m["loss"])
        # master params stay f32
        assert all(l.dtype == jnp.float32
                   for l in jax.tree.leaves(state.params))
        assert float(m["loss"]) < first

    def test_bf16_close_to_f32(self, pg):
        x, y = _batch(64)
        f32 = _mk(pg)
        b16 = _mk(pg, compute_dtype=jnp.bfloat16)
        s1, m1 = f32.train_step(f32.init(seed=0), x, y)
        s2, m2 = b16.train_step(b16.init(seed=0), x, y)
        # bf16 forward: loss agrees to ~1e-2
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=5e-2)


class TestFusedLossUnderDDP:
    def test_fused_ce_matches_unfused(self, pg):
        """CrossEntropyLoss(fused=True) — the Pallas CE kernel — inside the
        DDP shard_map step: regression for vma-annotated kernel outputs
        (the kernel is traced inside shard_map here)."""
        x, y = _batch(64)
        plain = _mk(pg)
        fused = DDP(ConvNet(), optimizer=optim.SGD(lr=0.05, momentum=0.9),
                    loss_fn=nn.CrossEntropyLoss(fused=True), group=pg,
                    donate=False)
        s_p, m_p = plain.train_step(plain.init(seed=0), x, y)
        s_f, m_f = fused.train_step(fused.init(seed=0), x, y)
        np.testing.assert_allclose(float(m_p["loss"]), float(m_f["loss"]),
                                   rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
            s_p.params, s_f.params)


class TestTrainChunk:
    def test_chunk_matches_sequential_steps(self, pg):
        """k steps in one dispatch (lax.scan) == k sequential train_step
        calls: same final params, same per-step losses."""
        k, B = 3, 64
        xs = jnp.stack([_batch(B, seed=i)[0] for i in range(k)])
        ys = jnp.stack([_batch(B, seed=i)[1] for i in range(k)])
        seq = _mk(pg)
        chk = _mk(pg)
        st = seq.init(seed=0)
        losses = []
        for i in range(k):
            st, m = seq.train_step(st, xs[i], ys[i])
            losses.append(float(m["loss"]))
        st_c, m_c = chk.train_chunk(chk.init(seed=0), xs, ys)
        assert m_c["loss"].shape == (k,)
        np.testing.assert_allclose(np.asarray(m_c["loss"]), losses, rtol=1e-5)
        assert int(st_c.step) == k
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7),
            st.params, st_c.params)

    def test_chunk_zero1_and_bf16(self, pg):
        """train_chunk composes with ZeRO-1 sharded opt state and bf16
        compute (the bench configuration)."""
        k, B = 2, 64
        xs = jnp.stack([_batch(B, seed=i)[0] for i in range(k)])
        ys = jnp.stack([_batch(B, seed=i)[1] for i in range(k)])
        ddp = _mk(pg, shard_optimizer=True, compute_dtype=jnp.bfloat16)
        st, m = ddp.train_chunk(ddp.init(seed=0), xs, ys)
        assert int(st.step) == k
        assert np.all(np.isfinite(np.asarray(m["loss"])))


class TestCommDtypeCompression:
    def test_bf16_comm_close_to_f32(self, pg):
        """Compressed all-reduce trains like the dense one (bf16 has ~3
        decimal digits; one step on equal inits stays close)."""
        x, y = _batch(64)
        dense = _mk(pg)
        comp = _mk(pg, comm_dtype=jnp.bfloat16)
        s1, m1 = dense.train_step(dense.init(seed=0), x, y)
        s2, m2 = comp.train_step(comp.init(seed=0), x, y)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)  # loss is pre-update
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-4),
            s1.params, s2.params)

    def test_wire_dtype_is_bf16(self, pg):
        """The lowered step's all-reduce ops carry bf16 operands iff
        comm_dtype is set (the compression is on the wire, not just in
        metadata)."""
        x, y = _batch(64)
        comp = _mk(pg, comm_dtype=jnp.bfloat16)
        st = comp.init(seed=0)
        text = comp._build_train_step(st).lower(st, x, y).as_text()
        assert "bf16" in text
        dense = _mk(pg)
        st2 = dense.init(seed=0)
        text2 = dense._build_train_step(st2).lower(st2, x, y).as_text()
        assert "bf16" not in text2

    def test_composes_with_zero1_and_accum(self, pg):
        x, y = _batch(64)
        ddp = _mk(pg, comm_dtype=jnp.bfloat16, shard_optimizer=True,
                  accum_steps=2)
        st, m = ddp.train_step(ddp.init(seed=0), x, y)
        assert np.isfinite(float(m["loss"]))
        st, m = ddp.train_step(st, x, y)
        assert np.isfinite(float(m["loss"]))
        # master params stay f32
        assert all(l.dtype == jnp.float32
                   for l in jax.tree.leaves(st.params))


class TestTrainRepeat:
    def test_repeat_matches_sequential_steps(self, pg):
        """k repeated steps on one batch == k sequential train_step calls
        with that batch."""
        k, B = 3, 64
        x, y = _batch(B)
        seq = _mk(pg)
        rep = _mk(pg)
        st = seq.init(seed=0)
        losses = []
        for _ in range(k):
            st, m = seq.train_step(st, x, y)
            losses.append(float(m["loss"]))
        st_r, m_r = rep.train_repeat(rep.init(seed=0), x, y, k)
        assert m_r["loss"].shape == (k,)
        np.testing.assert_allclose(np.asarray(m_r["loss"]), losses, rtol=1e-5)
        assert int(st_r.step) == k
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7),
            st.params, st_r.params)
        # loss falls across the repeated steps (it actually trains)
        assert float(m_r["loss"][-1]) < float(m_r["loss"][0])


class TestEvaluate:
    def test_evaluate_over_loader(self, pg):
        """ddp.evaluate drives eval_step over any (x, y) iterable and
        returns sample-weighted global metrics."""
        ddp = _mk(pg)
        st = ddp.init(seed=0)
        # plant a signal, train until it separates
        rng = np.random.default_rng(1)
        y = rng.integers(0, 10, 256).astype(np.int32)
        x = rng.normal(0, 0.3, (256, 28, 28, 1)).astype(np.float32)
        for c in range(10):
            idx = np.nonzero(y == c)[0]
            x[idx, 2 + (c // 5) * 12:6 + (c // 5) * 12,
              2 + (c % 5) * 5:6 + (c % 5) * 5, :] += 2.5
        xj, yj = jnp.asarray(x), jnp.asarray(y)
        st, _ = ddp.train_repeat(st, xj, yj, 25)
        res = ddp.evaluate(st, [(xj[:128], yj[:128]), (xj[128:], yj[128:])])
        assert res["count"] == 256
        assert res["accuracy"] > 0.9
        assert np.isfinite(res["loss"])
        # uneven final batch: padded to the first batch's size with
        # ignore_index labels — count and accuracy stay exact
        res2 = ddp.evaluate(st, [(xj[:128], yj[:128]), (xj[128:168], yj[128:168])])
        assert res2["count"] == 168
        exact = ddp.evaluate(st, [(xj[:168], yj[:168])])
        assert abs(res2["accuracy"] - exact["accuracy"]) < 1e-9


class TestEvaluateEdgeCases:
    def test_single_short_batch_padded_to_mesh(self, pg):
        """A lone batch not divisible by the device count is padded up
        (regression: first-batch divisibility)."""
        n_dev = pg.size()
        b = n_dev + 1 if n_dev > 1 else 3
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(b, 28, 28, 1)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, 10, b).astype(np.int32))
        ddp = _mk(pg)
        res = ddp.evaluate(ddp.init(seed=0), [(x, y)])
        assert res["count"] == b

    def test_sequence_labels(self, pg):
        """(batch, seq) labels: accuracy is per token, padding is
        rank-aware (regression: seq-model evaluate)."""
        from tpu_dist.models import TransformerLM
        from tpu_dist.parallel import DDP
        model = TransformerLM(vocab_size=17, dim=16, depth=1, num_heads=2,
                              max_seq_len=8)
        ddp = DDP(model, optimizer=optim.SGD(lr=0.1),
                  loss_fn=nn.CrossEntropyLoss(), group=pg, donate=False)
        st = ddp.init(seed=0)
        rng = np.random.default_rng(0)
        n_dev = pg.size()
        full, part = 2 * n_dev, n_dev + 1 if n_dev > 1 else 3
        xs = jnp.asarray(rng.integers(0, 17, (full + part, 8)))
        ys = jnp.asarray(rng.integers(0, 17, (full + part, 8)))
        res = ddp.evaluate(st, [(xs[:full], ys[:full]),
                                (xs[full:], ys[full:])])
        assert res["count"] == (full + part) * 8  # tokens, not rows
        assert 0.0 <= res["accuracy"] <= 1.0


class TestEvaluateIgnoreTokens:
    def test_data_inherent_ignore_tokens_excluded(self, pg):
        """Targets carrying real ignore_index padding (variable-length
        sequences): count and accuracy cover only scored tokens."""
        from tpu_dist.models import TransformerLM
        model = TransformerLM(vocab_size=17, dim=16, depth=1, num_heads=2,
                              max_seq_len=8)
        ddp = DDP(model, optimizer=optim.SGD(lr=0.1),
                  loss_fn=nn.CrossEntropyLoss(), group=pg, donate=False)
        st = ddp.init(seed=0)
        rng = np.random.default_rng(0)
        B = 2 * pg.size()
        xs = jnp.asarray(rng.integers(0, 17, (B, 8)))
        ys_np = rng.integers(0, 17, (B, 8))
        ys_np[:, 5:] = -100  # last 3 tokens of every row are padding
        ys = jnp.asarray(ys_np)
        res = ddp.evaluate(st, [(xs, ys)])
        assert res["count"] == B * 5  # only scored tokens
        # exact agreement with manual accuracy on the scored region
        logits = model.apply(st.params, xs)
        manual = float((jnp.argmax(logits[:, :5], -1) == ys[:, :5]).mean())
        assert abs(res["accuracy"] - manual) < 1e-6


class TestEvaluateCustomLossNoIgnore:
    def test_partial_batch_exact_without_ignore_index(self, pg):
        """A loss_fn with NO ignore_index attribute: evaluate masks batch
        padding positionally (true row count), so padded rows never enter
        the loss, the accuracy denominator, or the count (regression:
        ADVICE r2 — padded rows were scored for custom losses)."""
        def brier(logits, y):  # plain callable, no ignore_index attr
            onehot = jax.nn.one_hot(y, logits.shape[-1])
            return jnp.mean((jax.nn.softmax(logits) - onehot) ** 2)

        ddp = DDP(ConvNet(), optimizer=optim.SGD(lr=0.05),
                  loss_fn=brier, group=pg, donate=False)
        st = ddp.init(seed=0)
        x, y = _batch(168, seed=3)
        # batch 2 is partial → padded up to batch 1's size internally
        padded = ddp.evaluate(st, [(x[:128], y[:128]), (x[128:], y[128:])])
        exact = ddp.evaluate(st, [(x, y)])
        assert padded["count"] == 168
        assert abs(padded["accuracy"] - exact["accuracy"]) < 1e-9
        np.testing.assert_allclose(padded["loss"], exact["loss"], rtol=1e-5)


class TestEvaluateNonNegativeIgnore:
    def test_accuracy_bounded_with_valid_class_ignore(self, pg):
        """ignore_index that is a valid class id (torch permits it): ignored
        positions must not count as correct even when argmax lands on the
        ignore class (regression: accuracy could exceed 1.0)."""
        from tpu_dist.models import TransformerLM
        model = TransformerLM(vocab_size=8, dim=16, depth=1, num_heads=2,
                              max_seq_len=4)
        ddp = DDP(model, optimizer=optim.SGD(lr=0.1),
                  loss_fn=nn.CrossEntropyLoss(ignore_index=2), group=pg,
                  donate=False)
        st = ddp.init(seed=0)
        B = pg.size()
        xs = jnp.asarray(np.zeros((B, 4), np.int32))
        # make labels EQUAL the model's argmax, then mark half as ignored
        logits = model.apply(st.params, xs)
        ys = jnp.argmax(logits, -1).astype(jnp.int32)
        ys = ys.at[:, 2:].set(2)  # ignored positions (may match argmax)
        res = ddp.evaluate(st, [(xs, ys)])
        kept = int((np.asarray(ys) != 2).sum())
        assert res["count"] == kept
        assert res["accuracy"] <= 1.0

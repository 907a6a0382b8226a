"""The one-token state update as a Pallas kernel (tpu_dist/ops/delta_step.py,
ISSUE 41) against its definition, ``nn.deltanet.gated_delta_step``: the
kernel interpreted on the CPU, both ranks of the decay, the no-op row, the
state aliased to the result, and the predicate that chooses it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn
from tpu_dist.nn.deltanet import gated_delta_step, takes_step_kernel
from tpu_dist.ops.delta_step import delta_step

SHAPES = [(3, 4, 128, 128), (1, 32, 128, 128), (5, 2, 64, 128)]
RANKS = ["channel", "head"]


def _token(key, b, h, dk, dv, rank, key_heads=None):
    """One token's operands as a layer makes them: ``q``, ``k`` normalised,
    ``g <= 0`` a channel or a head, ``beta`` in (0, 1); ``key_heads`` < h:
    each key head repeated to its value heads."""
    ks = jax.random.split(key, 5)
    hk = key_heads or h
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6)
    q = unit(jax.random.normal(ks[0], (b, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, hk, dk)))
    q, k = (jnp.repeat(a, h // hk, axis=1) for a in (q, k))
    v = jax.random.normal(ks[2], (b, h, dv))
    g = -3.0 * jax.random.uniform(
        ks[3], (b, h, dk) if rank == "channel" else (b, h))
    return q, k, v, g, jax.random.uniform(ks[4], (b, h))


def _close(got, want, rel=1e-6):
    """To ``rel`` of the largest entry: float32 sums of 128 products in
    another order differ by a few 1e-7 of their scale."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=rel,
                               atol=rel * np.abs(want).max())


@pytest.mark.parametrize("start", ["zero", "random"])
@pytest.mark.parametrize("rank", RANKS)
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_kernel_equals_its_definition_over_chained_tokens(shape, rank, start):
    b, h, dk, dv = shape
    state = (jnp.zeros(shape, jnp.float32) if start == "zero"
             else jax.random.normal(jax.random.key(7), shape))
    want_s = got_s = state
    for t in range(8):
        tok = _token(jax.random.key(100 + t), b, h, dk, dv, rank)
        want_o, want_s = gated_delta_step(want_s, *tok)
        got_o, got_s = delta_step(got_s, *tok)
        assert got_o.dtype == got_s.dtype == jnp.float32
        _close(got_o, want_o)
        _close(got_s, want_s)


@pytest.mark.parametrize("rank", RANKS)
def test_a_no_op_row_keeps_its_state_bit_for_bit(rank):
    """``g = 0`` and ``beta = 0`` (a free slot's row, a padded position):
    rows 1 of 3 stays as it was, to the bit; the others move."""
    shape = (3, 4, 128, 128)
    state = jax.random.normal(jax.random.key(3), shape)
    q, k, v, g, beta = _token(jax.random.key(4), *shape, rank)
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    _, new = delta_step(state, q, k, v, g, beta)
    assert np.array_equal(np.asarray(new)[1], np.asarray(state)[1])
    assert not np.array_equal(np.asarray(new)[0], np.asarray(state)[0])
    assert not np.array_equal(np.asarray(new)[2], np.asarray(state)[2])


def test_repeated_key_heads_agree():
    """Qwen3-Next's form: 16 key heads serve 32 value heads, ``q`` and ``k``
    arrive repeated, the decay is a number a head."""
    shape = (2, 32, 128, 128)
    state = jax.random.normal(jax.random.key(5), shape)
    tok = _token(jax.random.key(6), *shape, "head", key_heads=16)
    want_o, want_s = gated_delta_step(state, *tok)
    got_o, got_s = delta_step(state, *tok)
    _close(got_o, want_o)
    _close(got_s, want_s)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, its sub-jaxprs' too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


@pytest.mark.parametrize("rank", RANKS)
def test_the_state_input_is_aliased_to_the_state_output(rank):
    shape = (2, 4, 128, 128)
    state = jnp.zeros(shape, jnp.float32)
    tok = _token(jax.random.key(8), *shape, rank)
    call, = _pallas_calls(jax.make_jaxpr(delta_step)(state, *tok).jaxpr)
    assert call.params["name"] == "delta_step"
    # operand 0, the state, is result 1, the new state
    assert tuple(call.params["input_output_aliases"]) == ((0, 1),)
    assert call.invars[0].aval.shape == call.outvars[1].aval.shape == shape


def test_under_jit_with_the_state_donated_the_result_is_the_same():
    shape = (3, 4, 128, 128)
    state = jax.random.normal(jax.random.key(9), shape)
    tok = _token(jax.random.key(10), *shape, "channel")
    want_o, want_s = delta_step(state, *tok)
    got_o, got_s = jax.jit(delta_step, donate_argnums=0)(state + 0.0, *tok)
    assert np.array_equal(np.asarray(got_o), np.asarray(want_o))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))


def _entry(shape=(2, 4, 128, 128), dtype=jnp.float32):
    return {"state": jax.ShapeDtypeStruct(shape, dtype)}


@pytest.mark.parametrize("impl, entry, t, want", [
    ("flash", _entry(), 1, True),
    ("flash", _entry((1, 32, 64, 256)), 1, True),
    ("flash", _entry(), 2, False),                       # t > 1: the scan
    ("flash", None, 1, False),                           # no cache entry
    ("flash", _entry(dtype=jnp.bfloat16), 1, False),
    ("flash", _entry((2, 4, 128, 96)), 1, False),        # Dv not whole lanes
    ("flash", _entry((2, 4, 20, 128)), 1, False),        # Dk not whole tiles
    ("dense", _entry(), 1, False),
    (None, _entry(), 1, False),                          # a CPU backend
], ids=["flash", "flash-64x256", "t2", "no-entry", "bf16", "dv96", "dk20",
        "dense", "cpu"])
def test_the_predicate_reads_the_call(impl, entry, t, want):
    if impl is None:
        assert takes_step_kernel(entry, t) is want
        return
    with nn.attention_impl(impl):
        assert takes_step_kernel(entry, t) is want
        for layer in (nn.GatedDeltaNet(32, 2, 4, 128, 128),
                      nn.KimiDeltaAttention(32, 4, 128)):
            assert layer.takes_step_kernel(entry, t) is want


# -- the two layers and the engine through the kernel --------------------------
# ``attention_impl("flash")`` makes a CPU run take the interpreted kernel;
# heads of 128 x 128 are the least it takes.

def _layer(kind):
    if kind == "kda":
        return nn.KimiDeltaAttention(64, 2, 128)
    return nn.GatedDeltaNet(64, 1, 2, 128, 128)     # a key head, two value


def _decode_steps(layer, params, xs, impl):
    """Feed ``xs`` (B, T, dim) one position a call through a cache entry;
    slot 1 is FREE (``valid`` false).  Outputs and the pool."""
    b = xs.shape[0]
    pool = {"": layer.init_cache(b)}
    valid = (jnp.arange(b) != 1)[:, None]
    outs = []
    with nn.attention_impl(impl):
        for t in range(xs.shape[1]):
            state = nn.cache.call_state(pool, jnp.full((b,), t, jnp.int32),
                                        valid=valid)
            out, state = layer.apply(params, xs[:, t:t + 1], state=state)
            pool, _ = nn.cache.split_state(state)
            outs.append(out)
    return jnp.concatenate(outs, 1), pool[""]


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_a_layers_decode_step_through_the_kernel_is_the_dense_forms(kind):
    layer = _layer(kind)
    params = layer.init(jax.random.key(1))
    xs = jax.random.normal(jax.random.key(2), (3, 4, 64))
    want_o, want = _decode_steps(layer, params, xs, "dense")
    got_o, got = _decode_steps(layer, params, xs, "flash")
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=1e-5)
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=0, atol=1e-5)
    # the free slot's state never moved, the busy ones' did
    assert not np.asarray(got["state"])[1].any()
    assert np.asarray(got["state"])[0].any()


def _hybrid(kind):
    from tpu_dist.models import KimiLinearLM, Qwen3NextLM
    if kind == "kda":
        return KimiLinearLM(
            97, dim=64, depth=2, num_heads=4, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            dense_hidden=96, kda_layers=[1], full_attn_layers=[2],
            linear_num_heads=2, linear_head_dim=128, num_experts=8,
            moe_top_k=2, moe_hidden=32, max_seq_len=64)
    return Qwen3NextLM(97, dim=64, depth=2, num_heads=2, num_kv_heads=1,
                       head_dim=16, full_attention_interval=2,
                       linear_key_heads=1, linear_value_heads=2,
                       linear_key_dim=128, linear_value_dim=128,
                       num_experts=4, moe_top_k=2, moe_hidden=16,
                       shared_hidden=16, max_seq_len=64)


def _serve(model, params, impl, prompts, new=5):
    """Three requests over four slots (one stays free) through
    ``SlotEngine`` built and run under ``impl``: tokens, ``stats()["state"]``
    before and after ``reset_stats()``."""
    from tpu_dist import serve
    got = {i: [] for i in range(len(prompts))}
    with nn.attention_impl(impl):
        engine = serve.SlotEngine(model, params, num_slots=4, max_len=64,
                                  min_bucket=16)
        for i, prompt in enumerate(prompts):
            engine.launch_admit(serve.Request(
                prompt, new, on_token=lambda _, tok, i=i: got[i].append(tok)))
            engine.settle()
        while not engine.idle():
            if engine.launch_step():
                engine.settle()
            else:
                engine.collect_all()
    state = engine.stats()["state"]
    engine.reset_stats()
    return got, state, engine.stats()["state"]


@pytest.mark.parametrize("kind", ["kda", "gdn"])
def test_slot_engine_serves_the_same_tokens_both_ways_and_counts(kind):
    model = _hybrid(kind)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, n) for n in (5, 11, 14)]
    want, dense, _ = _serve(model, params, "dense", prompts)
    got, flash, zeroed = _serve(model, params, "flash", prompts)
    assert got == want and all(len(t) == 5 for t in got.values())
    # four decode steps follow the prefills' first tokens
    assert (dense["steps"], dense["kernel_steps"]) == (4, 0)
    assert (flash["steps"], flash["kernel_steps"]) == (4, 4)
    assert flash["state_bytes"] == dense["state_bytes"] > 0
    assert (zeroed["steps"], zeroed["kernel_steps"]) == (0, 0)


def test_a_model_without_recurrent_layers_counts_no_kernel_step():
    from tpu_dist import serve
    from tpu_dist.models import TransformerLM
    model = TransformerLM(97, dim=32, depth=1, num_heads=2, max_seq_len=32)
    with nn.attention_impl("flash"):
        assert model.slot_state_kernel(model.init_slot_cache(2, 32)) is False
    engine = serve.SlotEngine(model, model.init(jax.random.key(0)),
                              num_slots=2, max_len=32)
    engine.admit(serve.Request(np.arange(1, 6), 3))
    while not engine.idle():
        engine.step()
    state = engine.stats()["state"]
    assert (state["steps"], state["kernel_steps"]) == (2, 0)
    # nor a prefill on the scan kernel (ISSUE 42), and the count is reset
    with nn.attention_impl("flash"):
        assert model.prefill_scan_kernel(model.init_slot_cache(2, 32),
                                         8) is False
    assert engine.stats()["prefill_scan"] == {"prefills": 1,
                                              "kernel_prefills": 0}
    engine.reset_stats()
    assert engine.stats()["prefill_scan"] == {"prefills": 0,
                                              "kernel_prefills": 0}


def test_the_wire_stats_frame_carries_the_two_counters():
    from tpu_dist import serve
    model = _hybrid("gdn")
    with nn.attention_impl("flash"):
        engine = serve.SlotEngine(model, model.init(jax.random.key(0)),
                                  num_slots=2, max_len=64, min_bucket=16)
        # the loop thread traces the programs: the override is a
        # process-wide stack, held open while it serves
        sched = serve.Scheduler(engine, batch_window=0.002)
        fe = serve.Frontend(sched, port=0)
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        try:
            cli.generate(list(range(1, 8)), max_new_tokens=4, timeout=120.0)
            state = cli.stats()["state"]
        finally:
            cli.close()
            fe.close()
            sched.close()
    assert state["steps"] == state["kernel_steps"] == 3
    assert state == engine.stats()["state"]

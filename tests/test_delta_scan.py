"""The scalar-decay chunked scan as a Pallas kernel (tpu_dist/ops/delta_scan.py,
ISSUE 42) against its definition, ``nn.deltanet.gated_delta_chunked``, and
against the token-by-token ``gated_delta_step``: the kernel interpreted on
the CPU, lengths under, at and over the chunk, key heads shared by value
heads, padded positions, planted decays, the state aliased to the result,
and the predicate that chooses it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn
from tpu_dist.nn.deltanet import (gated_delta_chunked, gated_delta_step,
                                  takes_scan_kernel)
from tpu_dist.ops.delta_scan import delta_scan

B, DK, DV = 2, 128, 128
LENGTHS = [1, 37, 64, 150, 512]


def _prompt(key, t, hk, hv, start="drawn", decay="drawn", b=B):
    """A prompt's operands as a layer makes them, ``(B, t, heads, .)``:
    ``q``, ``k`` normalised and by KEY head, ``g <= 0`` and ``beta`` in
    (0, 1) a value head; ``decay`` ``"strong"`` plants e^-20 a chunk."""
    ks = jax.random.split(key, 6)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6)
    q = unit(jax.random.normal(ks[0], (b, t, hk, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, hk, DK)))
    v = jax.random.normal(ks[2], (b, t, hv, DV))
    g = -2.0 * jax.random.uniform(ks[3], (b, t, hv))
    if decay == "strong":
        g = jnp.full((b, t, hv), -20.0 / 64)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    state = (jnp.zeros((b, hv, DK, DV), jnp.float32) if start == "zero"
             else jax.random.normal(ks[5], (b, hv, DK, DV)))
    return state, q, k, v, g, beta


def _chunked(state, q, k, v, g, beta):
    """The definition on the kernel's operands: ``q``, ``k`` repeated to
    the value heads, everything heads-first, the output back."""
    rep = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(a, rep, axis=2) for a in (q, k))
    out, state = gated_delta_chunked(state, *(
        jnp.moveaxis(a, 2, 1) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 1, 2), state


def _by_token(state, q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(a, rep, axis=2) for a in (q, k))

    def token(s, x):
        out, s = gated_delta_step(s, *x)
        return s, out

    state, out = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), state


def _close(got, want, rel=2e-5):
    """To ``rel`` of the largest entry: every product in the kernel is three
    bfloat16 passes (2^-17 of its scale), the definition's on the CPU are
    float32."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rel * np.abs(want).max())


@pytest.mark.parametrize("start", ["zero", "drawn"])
@pytest.mark.parametrize("heads", [(2, 2), (1, 2), (2, 4)],
                         ids=["rep1", "rep2", "rep2-two-pairs"])
@pytest.mark.parametrize("length", LENGTHS)
def test_kernel_equals_the_chunked_form_and_the_recurrence(length, heads,
                                                           start):
    """A grid step holds two pairs of value heads where the heads allow it
    (four value heads here), one pair otherwise, and up to four chunks."""
    args = _prompt(jax.random.key(length), length, *heads, start=start)
    got_o, got_s = delta_scan(*args)
    assert got_o.dtype == got_s.dtype == jnp.float32
    assert got_o.shape == (B, length, heads[1], DV)
    for form in (_chunked, _by_token):
        want_o, want_s = form(*args)
        _close(got_o, want_o)
        _close(got_s, want_s)


@pytest.mark.parametrize("start", ["zero", "drawn"])
@pytest.mark.parametrize("length", [64, 150])
def test_a_decay_of_e_minus_20_a_chunk(length, start):
    """A state that a chunk all but forgets: nothing overflows, and what is
    left of the starting state is the definition's."""
    args = _prompt(jax.random.key(5), length, 1, 2, start=start,
                   decay="strong")
    got_o, got_s = delta_scan(*args)
    want_o, want_s = _chunked(*args)
    assert np.isfinite(np.asarray(got_o)).all()
    _close(got_o, want_o)
    _close(got_s, want_s)


@pytest.mark.parametrize("heads", [(4, 4), (1, 4), (2, 6)],
                         ids=["rep1-two-pairs", "rep4", "rep3"])
def test_other_groupings_of_value_heads_by_key_head(heads):
    """Two pairs of a step that read four key heads, or one; a group of
    three (a pair would straddle two key heads: ``q`` and ``k`` are repeated
    for it)."""
    args = _prompt(jax.random.key(11), 150, *heads)
    got_o, got_s = delta_scan(*args)
    want_o, want_s = _chunked(*args)
    _close(got_o, want_o)
    _close(got_s, want_s)


def _padded(args, bucket):
    """The prompt in a bucket of ``bucket`` positions: ``g = 0`` and ``beta
    = 0`` past its end, anything in ``q``, ``k``, ``v`` there."""
    state, *ops = args
    t = ops[0].shape[1]
    junk = jax.random.normal(jax.random.key(bucket), (B, bucket - t))
    wide = []
    for i, a in enumerate(ops):
        fill = junk.reshape(B, bucket - t, *[1] * (a.ndim - 2))
        fill = jnp.broadcast_to(fill, (B, bucket - t) + a.shape[2:])
        wide.append(jnp.concatenate(
            [a, fill if i < 3 else jnp.zeros_like(fill)], axis=1))
    return (state, *wide)


@pytest.mark.parametrize("length", [37, 100])
def test_the_state_after_a_padded_prompt_is_the_state_after_its_last_token(
        length):
    """In two buckets, bit for bit the same state; and the definition's
    state after the prompt alone."""
    args = _prompt(jax.random.key(6), length, 1, 2)
    _, alone = delta_scan(*args)
    out128, in128 = delta_scan(*_padded(args, 128))
    out256, in256 = delta_scan(*_padded(args, 256))
    assert np.array_equal(np.asarray(in128), np.asarray(in256))
    assert np.array_equal(np.asarray(out128)[:, :length],
                          np.asarray(out256)[:, :length])
    _close(in128, alone, rel=1e-6)
    _close(in128, _chunked(*args)[1])


@pytest.mark.parametrize("length", [1, 64, 200])
def test_padded_positions_leave_the_state_bit_for_bit(length):
    """A call of nobody's positions alone (``g = 0``, ``beta = 0``): row 1
    of two keeps its state to the bit, row 0 moves."""
    state, q, k, v, g, beta = _prompt(jax.random.key(7), length, 2, 2)
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    _, new = delta_scan(state, q, k, v, g, beta)
    assert np.array_equal(np.asarray(new)[1], np.asarray(state)[1])
    assert not np.array_equal(np.asarray(new)[0], np.asarray(state)[0])


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, its sub-jaxprs' too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub))
    return found


@pytest.mark.parametrize("heads", [(2, 2), (1, 2)], ids=["rep1", "rep2"])
def test_the_state_input_is_aliased_to_the_state_output(heads):
    args = _prompt(jax.random.key(8), 128, *heads)
    call, = _pallas_calls(jax.make_jaxpr(delta_scan)(*args).jaxpr)
    assert call.params["name"] == "delta_scan"
    # operand 0, the state, is result 1, the new state
    assert tuple(call.params["input_output_aliases"]) == ((0, 1),)
    assert (call.invars[0].aval.shape == call.outvars[1].aval.shape
            == args[0].shape)
    # q and k go in by KEY head and in the layer's layout: no operand is a
    # repeated or heads-first copy
    assert [v.aval.shape for v in call.invars[1:4]] == [
        (B, 128, heads[0] * DK), (B, 128, heads[0] * DK),
        (B, 128, heads[1] * DV)]


def test_under_jit_with_the_state_donated_the_result_is_the_same():
    args = _prompt(jax.random.key(9), 100, 1, 2)
    want_o, want_s = delta_scan(*args)
    got_o, got_s = jax.jit(delta_scan, donate_argnums=0)(
        args[0] + 0.0, *args[1:])
    assert np.array_equal(np.asarray(got_o), np.asarray(want_o))
    assert np.array_equal(np.asarray(got_s), np.asarray(want_s))


def _entry(shape=(2, 4, 128, 128), dtype=jnp.float32):
    return {"state": jax.ShapeDtypeStruct(shape, dtype)}


def _g(t, per_channel=False):
    return jax.ShapeDtypeStruct((2, t, 4, 128) if per_channel else (2, t, 4),
                                jnp.float32)


@pytest.mark.parametrize("impl, entry, t, g, want", [
    ("flash", _entry(), 64, _g(64), True),
    ("flash", _entry(), 2, _g(2), True),
    ("flash", _entry((1, 32, 128, 256)), 4096, _g(4096), True),
    ("flash", _entry(), 1, _g(1), False),                # one token: the step
    ("flash", None, 64, _g(64), False),                  # no cache entry
    ("flash", _entry(dtype=jnp.bfloat16), 64, _g(64), False),
    ("flash", _entry((2, 4, 128, 96)), 64, _g(64), False),   # Dv not lanes
    ("flash", _entry((2, 4, 64, 128)), 64, _g(64), False),   # Dk not lanes
    ("flash", _entry(), 64, _g(64, per_channel=True), False),
    ("dense", _entry(), 64, _g(64), False),
    (None, _entry(), 64, _g(64), False),                 # a CPU backend
], ids=["flash", "t2", "flash-128x256", "t1", "no-entry", "bf16", "dv96",
        "dk64", "per-channel", "dense", "cpu"])
def test_the_predicate_reads_the_call(impl, entry, t, g, want):
    if impl is None:
        assert takes_scan_kernel(entry, t, g) is want
        return
    with nn.attention_impl(impl):
        assert takes_scan_kernel(entry, t, g) is want
        if len(g.shape) == 3:
            layer = nn.GatedDeltaNet(32, 2, 4, 128, 128)
            assert layer.takes_scan_kernel(entry, t) is want
        # a decay a channel never takes it
        assert nn.KimiDeltaAttention(32, 4, 128).takes_scan_kernel(
            entry, t) is False


# -- the layer through the kernel ----------------------------------------------
# ``attention_impl("flash")`` makes a CPU run take the interpreted kernel;
# heads of 128 x 128 are the least it takes.

def _prefill(layer, params, xs, n_real, impl):
    """One call of ``xs`` (B, T, dim) through a cache entry, ``n_real`` (B,)
    positions of each row real and leading.  Output and the pool entry."""
    b, t, _ = xs.shape
    pool = {"": layer.init_cache(b)}
    valid = jnp.arange(t)[None, :] < jnp.asarray(n_real)[:, None]
    with nn.attention_impl(impl):
        state = nn.cache.call_state(pool, jnp.zeros((b,), jnp.int32),
                                    valid=valid)
        out, state = layer.apply(params, xs, state=state)
    pool, _ = nn.cache.split_state(state)
    return out, pool[""]


@pytest.mark.parametrize("kind", ["gdn", "kda"])
def test_a_layers_prefill_through_the_kernel_is_the_jax_numpy_forms(kind):
    """Gated DeltaNet (a key head, two value heads) takes the kernel and
    agrees with the ``jax.numpy`` scan; Kimi Delta Attention never takes it
    (its jaxpr holds no ``delta_scan``) and is the same either way."""
    layer = (nn.KimiDeltaAttention(64, 2, 128) if kind == "kda"
             else nn.GatedDeltaNet(64, 1, 2, 128, 128))
    params = layer.init(jax.random.key(1))
    xs = jax.random.normal(jax.random.key(2), (2, 96, 64))
    want_o, want = _prefill(layer, params, xs, (96, 41), "dense")
    got_o, got = _prefill(layer, params, xs, (96, 41), "flash")
    np.testing.assert_allclose(got_o, want_o, rtol=0, atol=2e-5)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=0, atol=2e-5)

    def call(x):
        pool = {"": layer.init_cache(2)}
        with nn.attention_impl("flash"):
            state = nn.cache.call_state(pool, jnp.zeros((2,), jnp.int32))
            return layer.apply(params, x, state=state)[0]

    names = [c.params["name"]
             for c in _pallas_calls(jax.make_jaxpr(call)(xs).jaxpr)]
    assert names == (["delta_scan"] if kind == "gdn" else [])


def test_a_plain_forward_never_takes_the_kernel():
    """No cache entry: the differentiable ``jax.numpy`` form, whatever
    ``attention_impl`` says, and a gradient flows."""
    layer = nn.GatedDeltaNet(64, 1, 2, 128, 128)
    params = layer.init(jax.random.key(1))
    xs = jax.random.normal(jax.random.key(2), (1, 70, 64))
    with nn.attention_impl("flash"):
        loss = lambda p: jnp.sum(layer.apply(p, xs) ** 2)
        assert not _pallas_calls(jax.make_jaxpr(loss)(params).jaxpr)
        grads = jax.grad(loss)(params)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))

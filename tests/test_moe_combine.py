"""The combine of a share of the experts by the buffer's rows in token order
(tpu_dist/ops/moe_combine.py, nn/moe.py ``_combine_held_rows``) against the
row-gather form it stands in for (``_combine_rows``): the same function of
``out`` and ``w``, forward and backward, whatever the spread of the held
picks over the tokens.  The kernel runs interpreted here (the chip's compiler
takes it at the cells' shapes in tests/test_decode_layout.py).
"""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn
from tpu_dist.nn import moe
from tpu_dist.ops import moe_combine

F32 = 2e-6          # float32 sums of a token's picks in another order


def _maps(held, m_rows, seed):
    """``slot`` (k, N) and its inverse ``choice_for_slot`` (m_rows,) for a
    mask of held picks: each held pick owns one row of the buffer, rows in
    no order of the tokens (as an expert-sorted buffer has them)."""
    k, n = held.shape
    picks = np.flatnonzero(held.reshape(-1))
    assert len(picks) <= m_rows
    rows = np.random.default_rng(seed).permutation(m_rows)[:len(picks)]
    slot = np.full(k * n, m_rows, np.int32)
    slot[picks] = rows
    inverse = np.full(m_rows, k * n, np.int32)
    inverse[rows] = picks
    return jnp.asarray(slot.reshape(k, n)), jnp.asarray(inverse)


def _held(case, rng):
    """The held picks (k, N) of each case and the buffer's rows."""
    if case == "uniform_eighth":
        return rng.random((10, 300)) < 1 / 8, 640
    if case == "all_or_none_by_token":
        return np.broadcast_to(rng.random(200) < 0.3, (8, 200)).copy(), 768
    if case == "padding_run_all_held":
        held = rng.random((4, 420)) < 1 / 8
        held[:, 120:] = True            # 300 rows alike, every pick held
        return held, 1408
    if case == "none_held":
        return np.zeros((8, 130), bool), 256
    if case == "a_tile_over_many_chunks":
        # one tile of 128 tokens, all four picks held: a run of 512 rows,
        # four chunks, between tiles that hold little
        held = rng.random((4, 384)) < 0.05
        held[:, 128:256] = True
        return held, 640
    if case == "rows_off_the_chunk":
        return rng.random((4, 96)) < 0.25, 200
    raise AssertionError(case)


CASES = ["uniform_eighth", "all_or_none_by_token", "padding_run_all_held",
         "none_held", "a_tile_over_many_chunks", "rows_off_the_chunk"]


@pytest.fixture(params=CASES)
def operands(request):
    rng = np.random.default_rng(CASES.index(request.param))
    held, m_rows = _held(request.param, rng)
    slot, inverse = _maps(held, m_rows, 1)
    k, n = held.shape
    out = jnp.asarray(rng.standard_normal((m_rows, 48)), jnp.float32)
    w = jnp.asarray(rng.random((k, n)), jnp.float32)
    return out, w, inverse, slot


def test_the_combine_by_rows_is_the_combine_by_picks(operands):
    out, w, inverse, slot = operands
    want = moe._combine_rows(out, w, inverse, slot)
    got = jax.jit(moe._combine_held_rows)(out, w, inverse, slot)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=0, atol=F32 * 8)


def test_its_gradients_are_the_row_gather_forms(operands):
    out, w, inverse, slot = operands
    gy = jax.random.normal(jax.random.key(2), (slot.shape[1], out.shape[1]))

    def loss(combine):
        return lambda out, w: jnp.sum(combine(out, w, inverse, slot) * gy)

    want = jax.grad(loss(moe._combine_rows), argnums=(0, 1))(out, w)
    got = jax.jit(jax.grad(loss(moe._combine_held_rows), argnums=(0, 1)))(
        out, w)
    for g, ref in zip(got, want):
        np.testing.assert_allclose(g, ref, rtol=0, atol=F32 * 8)
    # and both are the plain sum's
    def plain(out, w):
        rows = jnp.concatenate([out, jnp.zeros_like(out[:1])])[slot]
        return jnp.sum((rows * w[:, :, None]).sum(0) * gy)
    for g, ref in zip(got, jax.grad(plain, argnums=(0, 1))(out, w)):
        np.testing.assert_allclose(g, ref, rtol=0, atol=F32 * 8)


def test_bfloat16_rounds_the_sum_once():
    """In bfloat16 the sum is accumulated in float32 and rounded once: at
    least as near the float32 sum as the row-gather form, which rounds every
    product first."""
    rng = np.random.default_rng(7)
    held, m_rows = _held("uniform_eighth", rng)
    slot, inverse = _maps(held, m_rows, 3)
    out = jnp.asarray(rng.standard_normal((m_rows, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.random(held.shape), jnp.bfloat16)
    exact = moe._combine_rows(out.astype(jnp.float32), w.astype(jnp.float32),
                              inverse, slot)
    got = moe._combine_held_rows(out, w, inverse, slot)
    old = moe._combine_rows(out, w, inverse, slot)
    assert got.dtype == jnp.bfloat16
    err = lambda y: float(jnp.abs(y.astype(jnp.float32) - exact).max())
    assert err(got) <= err(old) and err(got) <= 2.0 ** -7 * float(
        jnp.abs(exact).max())


def test_token_order_lists_each_held_pick_once_in_token_order():
    rng = np.random.default_rng(11)
    held, m_rows = _held("padding_run_all_held", rng)
    slot, _ = _maps(held, m_rows, 5)
    places = 1408
    dest, rows, ends = jax.jit(
        moe_combine.token_order, static_argnums=(1, 2))(slot, m_rows, places)
    dest, rows, ends = map(np.asarray, (dest, rows, ends))
    total = int(held.sum())
    assert ends[-1] == total and (rows[total:] < m_rows).all()
    # token-major: a token's held picks in the order of its choices
    want = np.asarray(slot).T[held.T]
    np.testing.assert_array_equal(rows[:total], want)
    np.testing.assert_array_equal(np.sort(dest[dest >= 0]), np.arange(total))
    np.testing.assert_array_equal(dest.reshape(-1)[held.T.reshape(-1)],
                                  np.arange(total))
    assert (dest[~held.T] == -1).all()


def _share_layer(held, experts, top_k):
    return nn.MoELayer(32, experts, hidden=16, top_k=top_k, dispatch="dropless",
                       gated=True, experts_held=held)


@pytest.mark.parametrize("tokens, by_rows", [(512, True), (128, False)],
                         ids=["a_prefill_by_rows", "a_small_call_by_picks"])
def test_the_counter_reads_the_rows_the_combine_gathered(tokens, by_rows):
    """A layer that holds 2 of 32 experts, 8 picks a token: a call of 4,096
    picks is combined by the rows of its buffer (twice the expected share and
    a block an expert: 768), one of 1,024 picks by the picks; the counter
    reads the one and the other, and both are the plain layer's output."""
    layer = _share_layer(2, 32, 8)
    kn = tokens * 8
    sizes = layer._buffer_sizes(kn, layer._block_rows(kn, jnp.float32))
    assert moe._combines_by_token(sizes[0], kn) is by_rows
    assert sizes[0] == 768 or not by_rows
    p = layer.init(jax.random.key(3))[""]
    x = jax.random.normal(jax.random.key(4), (tokens, 32))
    state = {"": dict(layer.init_counters(), valid=jnp.ones(tokens, bool))}
    run = lambda: jax.jit(
        lambda p, x: layer.apply({"": p}, x, state=state))(p, x)
    out, new = run()
    c = jax.tree.map(np.asarray, new[""])
    assert c["computed_rows"] <= sizes[0]           # the usual buffer took it
    # 4,096 picks on 2 of 32 experts: ~256 held, which fit half the rows
    half, whole = moe_combine.gather_sizes(sizes[0])
    assert c["held_rows"] <= half == 384 or not by_rows
    assert c["combined_rows"] == (half if by_rows else kn)
    # the same layer by the picks alone
    with mock.patch.object(moe, "_combines_by_token", lambda m, kn: False):
        want, old = run()
    assert np.asarray(old[""]["combined_rows"]) == kn
    np.testing.assert_allclose(out, want, rtol=0, atol=F32 * 8)


def test_a_share_sent_more_than_the_usual_buffer_takes_the_next_rung():
    """The ladder's predicate decides the combine too: rows that pass the
    usual buffer take the middle one, combined by ITS rows while it is at
    most half the picks."""
    layer = _share_layer(2, 64, 8)
    p = dict(layer.init(jax.random.key(3))[""])
    x = jax.random.normal(jax.random.key(4), (512, 32))
    sizes = layer._buffer_sizes(4096, layer._block_rows(4096, jnp.float32))
    assert sizes == [384, 1536, 4224]
    state = {"": dict(layer.init_counters(), valid=jnp.ones(512, bool))}
    # 300 tokens send two of their picks to the two held experts: 300 rows
    # each, 320 in blocks of 64, past the usual 384 together
    x_mid = x.at[:300, 0].set(6.0)
    p["router"] = p["router"].at[0, :2].set(10.0)
    run = lambda: jax.jit(
        lambda p, x: layer.apply({"": p}, x, state=state))(p, x_mid)
    out, new = run()
    c = jax.tree.map(np.asarray, new[""])
    assert 384 < c["computed_rows"] <= 1536
    # ~650 held picks fit half of the middle buffer's rows
    assert c["held_rows"] <= 768 and c["combined_rows"] == 768
    with mock.patch.object(moe, "_combines_by_token", lambda m, kn: False):
        want, _ = run()
    np.testing.assert_allclose(out, want, rtol=0, atol=F32 * 8)


def test_a_row_that_is_not_finite_stays_in_its_tile_of_tokens():
    """A chunk of gathered rows is shared by neighbouring tiles of tokens
    and holds places nobody owns: the kernel zeroes what is not the tile's
    own, so an infinity in a row no pick owns reaches nobody and a NaN in one
    token's row reaches that token's tile of 128 (through ``0 x NaN`` in the
    tile's product) and no other."""
    rng = np.random.default_rng(13)
    held, m_rows = _held("uniform_eighth", rng)
    slot, inverse = _maps(held, m_rows, 9)
    out = np.asarray(rng.standard_normal((m_rows, 48)), np.float32)
    w = jnp.asarray(rng.random(held.shape), jnp.float32)
    owned = np.asarray(slot)[np.asarray(slot) < m_rows]
    out[np.setdiff1d(np.arange(m_rows), owned)] = np.inf
    clean = np.asarray(moe._combine_held_rows(jnp.asarray(out), w, inverse,
                                              slot))
    assert np.isfinite(clean).all()
    # the first token of the second tile that holds a pick
    token = 128 + int(np.flatnonzero(held[:, 128:256].any(0))[0])
    bad = np.asarray(slot)[:, token]
    out[bad[bad < m_rows][0]] = np.nan
    got = np.asarray(moe._combine_held_rows(jnp.asarray(out), w, inverse,
                                            slot))
    assert np.isnan(got[token]).all()
    np.testing.assert_array_equal(got[:128], clean[:128])
    np.testing.assert_array_equal(got[256:], clean[256:])

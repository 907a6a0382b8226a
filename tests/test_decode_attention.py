"""ops/decode_attention: the slot-decode kernel against the dense branch.

The kernel runs here under the Pallas interpreter at small shapes, forced
with ``attention_impl("flash")``; the comparison is with the dense branch of
``MultiheadSelfAttention._decode`` (forced with ``"dense"``) on the SAME
layer, parameters and K/V pool.  What the chip's compiler makes of the
kernel at the serving cells' shapes is ``tests/test_decode_layout.py``'s.

Grouped queries (ISSUE 45): ``G`` query heads a K/V head take the kernel's
grouped form, one copy of a K/V block for all ``G`` rows and two MXU products
a head; ``G`` = 1 keeps the one-row VPU form.  Both stand against the same
dense branch, whose grouped einsums have K/V head ``j`` serve query heads
``[j * G, (j + 1) * G)``.

Tolerances.  float32: the kernel and the dense branch both accumulate in
float32 and differ only in summation order: 1e-5.  bfloat16: the kernel
keeps float32 scores and statistics and rounds its output ONCE to bf16; the
dense branch rounds scores, probabilities and output to bf16 each (8
mantissa bits: relative 2**-8 a rounding).  With outputs of magnitude up to
~1 after the output projection, three roundings bound the difference by
about 3 * 2**-8 = 1.2e-2; 3e-2 leaves room for the projection's own sums.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn
from tpu_dist.ops.decode_attention import (BLOCK_K, decode_attention,
                                            kv_blocks)

TMAX = 512
# free, first column, the 128-lane edges, the block (256) edges, the row's
# last column, and a full row (the new column is dropped)
LENGTHS = [0, 1, 127, 128, 129, 255, 256, 257, TMAX - 1, TMAX]
# query heads, K/V heads, head dim, the pool's (and the layer's) type, tolerance
SHAPES = {"5x64-float32": (5, 5, 64, jnp.float32, 1e-5),
          "4x128-float32": (4, 4, 128, jnp.float32, 1e-5),
          "5x64-bfloat16": (5, 5, 64, jnp.bfloat16, 3e-2),
          "4x128-bfloat16": (4, 4, 128, jnp.bfloat16, 3e-2),
          "2x256-bfloat16": (2, 2, 256, jnp.bfloat16, 3e-2),
          # G = 5 (Falcon-H1's 20 over 4) and G = 8 (Qwen3-Next's 16 over 2)
          "10over2x128-float32": (10, 2, 128, jnp.float32, 1e-5),
          "10over2x128-bfloat16": (10, 2, 128, jnp.bfloat16, 3e-2),
          "8over1x256-float32": (8, 1, 256, jnp.float32, 1e-5),
          "16over2x256-bfloat16": (16, 2, 256, jnp.bfloat16, 3e-2),
          "5over1x256-bfloat16": (5, 1, 256, jnp.bfloat16, 3e-2),
          "8over1x128-bfloat16": (8, 1, 128, jnp.bfloat16, 3e-2)}


@pytest.fixture(scope="module", params=list(SHAPES))
def case(request):
    """One decode step of one attention layer over a random pool, through
    both branches: ``(lengths, pool before, dense (out, state), kernel
    (out, state), tolerance)``."""
    heads, kv_heads, dim, dtype, tol = SHAPES[request.param]
    attn = nn.MultiheadSelfAttention(heads * dim, heads, causal=True,
                                     num_kv_heads=kv_heads)
    keys = jax.random.split(jax.random.key(7), 4)
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                    attn.init(keys[0]))
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    b = len(LENGTHS)
    pool = attn.init_cache(b, TMAX, dtype)
    pool = dict(pool, k=jax.random.normal(keys[1], pool["k"].shape, dtype),
                v=jax.random.normal(keys[2], pool["v"].shape, dtype))
    x = jax.random.normal(keys[3], (b, 1, heads * dim), dtype)

    def run(impl):
        with nn.attention_impl(impl):
            out, st = jax.jit(lambda p, x, s: attn.apply(p, x, state=s))(
                params, x, {attn._path: dict(pool, index=lengths)})
        return np.asarray(out, np.float32)[:, 0], st[attn._path]

    return lengths, pool, run("dense"), run("flash"), tol


@pytest.mark.parametrize("length", [n for n in LENGTHS if n > 0])
def test_output_equals_the_dense_branch(case, length):
    _, _, (dense, _), (kernel, _), tol = case
    b = LENGTHS.index(length)
    np.testing.assert_allclose(kernel[b], dense[b], atol=tol, rtol=0)


@pytest.mark.parametrize("length", [n for n in LENGTHS if 0 < n < TMAX])
def test_pool_holds_the_new_column_and_nothing_else_changed(case, length):
    """Bit for bit: outside column ``length`` the row is the input's, and
    the column is the one the dense branch wrote."""
    _, pool, (_, dense), (_, kernel), _ = case
    b = LENGTHS.index(length)
    for key in ("k", "v"):
        before = np.asarray(pool[key][b], np.float32)
        after = np.asarray(kernel[key][b], np.float32)
        wrote = np.asarray(dense[key][b], np.float32)
        np.testing.assert_array_equal(after[..., length], wrote[..., length])
        assert not np.array_equal(after[..., length], before[..., length])
        keep = np.arange(TMAX) != length
        np.testing.assert_array_equal(after[..., keep], before[..., keep])


def test_a_column_at_tmax_is_dropped(case):
    _, pool, _, (_, kernel), _ = case
    b = LENGTHS.index(TMAX)
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(kernel[key][b], np.float32),
                                      np.asarray(pool[key][b], np.float32))


def test_a_free_slot_is_untouched_and_its_output_finite(case):
    """The kernel's own row for a free slot is zero
    (``test_free_slots_anywhere_in_the_pool``); here, after the output
    projection, it is the projection's bias: finite."""
    _, pool, _, (out, kernel), _ = case
    b = LENGTHS.index(0)
    assert np.isfinite(out).all()
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(kernel[key][b], np.float32),
                                      np.asarray(pool[key][b], np.float32))


def test_index_advances_by_one(case):
    lengths, _, (_, dense), (_, kernel), _ = case
    np.testing.assert_array_equal(kernel["index"], np.asarray(lengths) + 1)
    np.testing.assert_array_equal(kernel["index"], dense["index"])


@pytest.mark.parametrize("group", [1, 5], ids=["G1", "G5"])
@pytest.mark.parametrize("lengths", [[0, 0, 0, 0], [0, 0, 9, 0],
                                     [300, 0, 0, 1]],
                         ids=["all-free", "leading-free", "trailing-free"])
def test_free_slots_anywhere_in_the_pool(lengths, group):
    """The work list holds busy slots only: a pool with none, with free
    slots before the first busy one and after the last.  A free slot's
    pool row is untouched and its output row zero, in both forms."""
    h, d, t = 2, 64, 512
    keys = jax.random.split(jax.random.key(3), 5)
    q = jax.random.normal(keys[0], (4, h * group, d))
    kn, vn = (jax.random.normal(k, (4, h, d)) for k in keys[1:3])
    kp, vp = (jax.random.normal(k, (4, h, d, t)) for k in keys[3:])
    lens = jnp.asarray(lengths, jnp.int32)
    with nn.attention_impl("flash"):
        out, k2, v2 = jax.jit(decode_attention)(q, kn, vn, kp, vp, lens)
    assert np.isfinite(np.asarray(out)).all()
    for b, n in enumerate(lengths):
        want_k, want_v = np.array(kp[b]), np.array(vp[b])
        if n:
            want_k[..., n], want_v[..., n] = kn[b], vn[b]
            s = np.einsum("hgd,hdt->hgt", np.reshape(q[b], (h, group, d)),
                          want_k[..., :n + 1]) / 8.0
            w = np.exp(s - s.max(-1, keepdims=True))
            want = np.einsum("hgt,hdt->hgd", w / w.sum(-1, keepdims=True),
                             want_v[..., :n + 1])
            np.testing.assert_allclose(out[b], want.reshape(h * group, d),
                                       atol=1e-5)
        else:
            assert not np.asarray(out[b]).any()
        np.testing.assert_array_equal(k2[b], want_k)
        np.testing.assert_array_equal(v2[b], want_v)


@pytest.mark.parametrize("lengths, want", [
    ([0, 0, 0], 0), ([1, 0, 0], 1), ([BLOCK_K - 1, BLOCK_K, 0], 3),
    ([TMAX - 1, TMAX, 5], 2 * (TMAX // BLOCK_K) + 1)],
    ids=["free", "one", "block-edge", "row-end"])
def test_kv_blocks_counts_what_the_work_list_holds(lengths, want):
    """The engine's counter is host arithmetic; the kernel's work list is
    device arithmetic: one formula, ``ceil((len + 1) / block)`` clipped to
    the row, nothing for a free slot."""
    from tpu_dist.ops.decode_attention import _work_list
    read, pool, block = kv_blocks(np.asarray(lengths, np.int32), TMAX)
    assert (read, pool, block) == (want, 3 * (TMAX // BLOCK_K), BLOCK_K)
    scalars, g = _work_list(jnp.asarray(lengths, jnp.int32), TMAX, block)
    assert g == pool and int(scalars[-1]) == want


@pytest.mark.parametrize("why, index, t, dtype, dim, tmax, impl, taken", [
    ("slot decode, forced", (4,), 1, jnp.bfloat16, 64, 256, "flash", True),
    ("a CPU run is dense", (4,), 1, jnp.bfloat16, 64, 256, None, False),
    ("forced dense", (4,), 1, jnp.bfloat16, 64, 256, "dense", False),
    ("prefill", (), 8, jnp.bfloat16, 64, 256, "flash", False),
    ("multi-token append", (4,), 2, jnp.bfloat16, 64, 256, "flash", False),
    ("int8 cache", (4,), 1, jnp.int8, 64, 256, "flash", False),
    ("D off the sublane tile", (4,), 1, jnp.bfloat16, 8, 256, "flash", False),
    ("Tmax off the lanes", (4,), 1, jnp.float32, 64, 192, "flash", False),
    ("grouped queries, forced", (4,), 1, jnp.bfloat16, 64, 256, "flash",
     True),
    ("grouped queries on a CPU run", (4,), 1, jnp.bfloat16, 64, 256, None,
     False),
    ("grouped queries forced dense", (4,), 1, jnp.bfloat16, 64, 256, "dense",
     False),
    ("grouped queries prefill", (), 8, jnp.bfloat16, 64, 256, "flash", False),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) and " " in v
    else None)
def test_which_branch_decode_takes(why, index, t, dtype, dim, tmax, impl,
                                   taken):
    """What ``_decode`` observes picks the branch; the traced program holds
    the kernel's call or does not.  Six query heads over two K/V heads where
    the row says grouped, two over two elsewhere."""
    import contextlib
    kv_heads, b = 2, 4
    heads = 6 if why.startswith("grouped") else kv_heads
    attn = nn.MultiheadSelfAttention(heads * dim, heads, causal=True,
                                     num_kv_heads=kv_heads)
    params = attn.init(jax.random.key(0))
    state = {attn._path: dict(attn.init_cache(b, tmax, dtype),
                              index=jnp.zeros(index, jnp.int32) + 3)}
    x = jnp.zeros((b, t, heads * dim))
    force = nn.attention_impl(impl) if impl else contextlib.nullcontext()
    with force:
        text = str(jax.make_jaxpr(
            lambda p, x, s: attn.apply(p, x, state=s))(params, x, state))
    assert ("decode_attention" in text) == taken, why

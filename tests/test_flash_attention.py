"""Flash-attention kernel vs the dense composition.

Runs the Pallas kernels in interpret mode on the CPU mesh (conftest forces
JAX_PLATFORMS=cpu): same kernel code as the TPU path, checked for forward
and gradient equality against tpu_dist.nn.attention's dense math.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist.nn.attention import scaled_dot_product_attention
from tpu_dist.ops import flash_attention, flash_attention_with_lse

# ``tpu_dist.ops.flash_attention`` the attribute is the function
fa = importlib.import_module("tpu_dist.ops.flash_attention")

# the older, compile-heavy cases stay out of the fast tier (`pytest -m "not
# slow"`); the sub-tile cases below them are tier-1
slow = pytest.mark.slow


def _rand_qkv(rng, b, tq, tk, h, d, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((b, tq, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, tk, h, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, tk, h, d)), dtype)
    return q, k, v


@slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    (2, 128, 128, 2, 64),     # exact tiles
    (1, 100, 100, 3, 48),     # ragged T and D -> padding paths
    (2, 96, 160, 2, 32),      # cross-attention Tq != Tk
    (1, 320, 320, 2, 64),     # 3x3 tile grid: online-softmax carry + causal
                              # tile-skip (blocks forced to 128 below)
])
def test_forward_matches_dense(rng, causal, shape):
    b, tq, tk, h, d = shape
    q, k, v = _rand_qkv(rng, b, tq, tk, h, d)
    # block_q/k=128 so T>128 shapes genuinely sweep multiple tiles (the
    # defaults would clamp to a single tile at these sizes)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = scaled_dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@slow
def test_forward_bf16(rng):
    q, k, v = _rand_qkv(rng, 2, 256, 256, 2, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    assert out.dtype == jnp.bfloat16
    ref = scaled_dot_product_attention(q.astype(jnp.float32),
                                       k.astype(jnp.float32),
                                       v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(out.astype(np.float32), ref, atol=3e-2,
                               rtol=3e-2)


@slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    (1, 128, 128, 2, 32),
    (1, 72, 136, 2, 24),      # ragged + cross-attention
    (1, 288, 288, 1, 64),     # 3x3 tile grid in both bwd kernels (blocks 128)
])
def test_grads_match_dense(rng, causal, shape):
    b, tq, tk, h, d = shape
    q, k, v = _rand_qkv(rng, b, tq, tk, h, d)
    cot = jnp.asarray(rng.standard_normal((b, tq, h, d)), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal=causal,
                                        block_q=128, block_k=128), cot)

    def loss_dense(q, k, v):
        return jnp.vdot(
            scaled_dot_product_attention(q, k, v, causal=causal), cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(gf, gd, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name}")


@slow
def test_jit_and_leading_batch_dims(rng):
    # extra leading dims + under jit (the TransformerLM call pattern)
    q = jnp.asarray(rng.standard_normal((2, 3, 64, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 3, 64, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 3, 64, 2, 32)), jnp.float32)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v)
    ref = scaled_dot_product_attention(q, k, v, causal=True)
    assert out.shape == (2, 3, 64, 2, 32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@slow
def test_sdpa_impl_flash_dispatch(rng):
    q, k, v = _rand_qkv(rng, 1, 64, 64, 2, 32)
    out = scaled_dot_product_attention(q, k, v, causal=True, impl="flash")
    ref = scaled_dot_product_attention(q, k, v, causal=True, impl="dense")
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError):
        mask = jnp.ones((64, 64), bool)
        scaled_dot_product_attention(q, k, v, mask=mask, impl="flash")


@slow
@pytest.mark.parametrize("t, block", [
    (64, 1024),     # one grid tile a head
    (256, 128),     # 2 x 2 grid tiles: a grid step picks its kind
])
def test_flash_under_shard_map(rng, eight_devices, t, block):
    # the DDP-wrapper path: pallas_call traced inside shard_map requires
    # vma-annotated out_shapes (regression test for the _out_struct fix)
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((8,), ("data",))
    q, k, v = _rand_qkv(rng, 16, t, t, 2, 32)

    def local_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=block,
                            block_k=block)
        return jax.lax.pmean(jnp.sum(o ** 2), "data")

    loss_fn = jax.jit(jax.shard_map(
        lambda q, k, v: jax.value_and_grad(local_loss)(q, k, v),
        mesh=mesh, in_specs=(P("data"),) * 3,
        out_specs=(P(), P("data"))))
    sh = NamedSharding(mesh, P("data"))
    loss, dq = loss_fn(*(jax.device_put(x, sh) for x in (q, k, v)))

    ref_loss, ref_dq = jax.value_and_grad(
        lambda q: jnp.mean(jnp.sum(
            scaled_dot_product_attention(q, k, v, causal=True) ** 2,
            axis=(1, 2, 3))))(q)
    np.testing.assert_allclose(float(loss), float(ref_loss) * 16 / 8,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(ref_dq) * 2,
                               atol=1e-4, rtol=1e-4)


@slow
def test_broadcast_kv_rejected(rng):
    # numpy-broadcast batch dims (shared KV) would silently misalign the
    # (B*H, T, D) flatten — must raise, and auto-dispatch must go dense
    q = jnp.asarray(rng.standard_normal((2, 64, 2, 32)), jnp.float32)
    kv = jnp.asarray(rng.standard_normal((1, 64, 2, 32)), jnp.float32)
    with pytest.raises(ValueError, match="batch/head"):
        flash_attention(q, kv, kv)
    # dense path still supports it (and auto never routes this to flash)
    out = scaled_dot_product_attention(q, kv, kv, causal=True)
    assert out.shape == q.shape


# ---------------------------------------------------------------------------
# sub-tiles inside the grid step (tier-1)
# ---------------------------------------------------------------------------

def _tol(dtype):
    # bf16 operands against the float32 dense composition: the forward bound
    # test_forward_bf16 uses; gradients of a unit-normal cotangent sum ~T
    # bf16 products
    return (dict(atol=2e-5, rtol=2e-5), dict(atol=5e-4, rtol=5e-4)) \
        if dtype == jnp.float32 else \
        (dict(atol=3e-2, rtol=3e-2), dict(atol=8e-2, rtol=8e-2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", [
    (1024, 1024, 64),   # the training cells' class: bf16 is ONE 1024 block of
                        # 4 x 4 sub-tiles, f32 2 x 2 blocks of 512 (a
                        # diagonal kind and a below-the-diagonal kind)
    (1000, 1000, 64),   # padding ends inside the last sub-tile
    (320, 320, 64),     # a 384 block walks in 128 sub-tiles
    (100, 100, 48),     # T below one sub-tile, ragged D
    (96, 160, 32),      # tq != tk
    (640, 384, 64),     # tq > tk: the last rows see every key
], ids=lambda s: "x".join(map(str, s)))
def test_subtiled_causal_matches_dense(rng, shape, dtype):
    """Forward and all three gradients of the sub-tiled causal path at the
    default 1024 blocks against the dense composition in float32."""
    tq, tk, d = shape
    q, k, v = _rand_qkv(rng, 1, tq, tk, 2, d, dtype)
    cot = jnp.asarray(rng.standard_normal((1, tq, 2, d)), jnp.float32)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    fwd, bwd = _tol(dtype)

    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == dtype
    ref = scaled_dot_product_attention(*f32, causal=True)
    np.testing.assert_allclose(out.astype(np.float32), ref, **fwd)

    g_flash = jax.grad(lambda *a: jnp.vdot(
        flash_attention(*a, causal=True).astype(jnp.float32), cot),
        argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(lambda *a: jnp.vdot(
        scaled_dot_product_attention(*a, causal=True), cot),
        argnums=(0, 1, 2))(*f32)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(gf.astype(np.float32), gd, **bwd,
                                   err_msg=f"d{name}")


def _dense_with_lse(q, k, v, causal, block):
    """(out, lse) of (B, T, H, D) inputs in plain jnp, float32, under the
    kernel's three-valued ``causal`` and its -1e30 convention for a row with
    no visible key."""
    tq, tk, d = q.shape[1], k.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    qpos, kpos = jnp.arange(tq)[:, None], jnp.arange(tk)[None, :]
    if causal is True:
        mask = kpos <= qpos
    elif causal == "offdiag":
        mask = kpos // block < qpos // block
    else:
        mask = jnp.ones((tq, tk), bool)
    s = jnp.where(mask, s, -jnp.inf)
    seen = mask.any(axis=1)[None, None, :, None]
    lse = jax.nn.logsumexp(jnp.where(seen, s, 0.0), axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - lse), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    lse = jnp.where(seen, lse, -1e30)[..., 0]
    return out, jnp.swapaxes(lse, 1, 2)                 # lse (B, Tq, H)


def _lse_loss(o, lse):
    # the lse cotangent is live; rows with no visible key (lse -1e30) are
    # left out of it, as ring attention's merge weight leaves them out
    return (o ** 2).sum() + 0.01 * (jnp.where(lse > -1e29, lse, 0.0) ** 2).sum()


@pytest.mark.parametrize("shape", [(1024, 1024), (900, 700)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("causal", [False, "offdiag", True], ids=str)
def test_lse_modes_match_dense(rng, causal, shape):
    """out AND lse AND the gradients with a live lse cotangent, for the three
    values of ``causal``, over 2 x 2 grid tiles of 2 x 2 sub-tiles."""
    tq, tk = shape
    q, k, v = _rand_qkv(rng, 1, tq, tk, 2, 32)
    kw = dict(causal=causal, block_q=512, block_k=512)
    o, lse = flash_attention_with_lse(q, k, v, **kw)
    ro, rlse = _dense_with_lse(q, k, v, causal, 512)
    np.testing.assert_allclose(o, ro, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, rlse, atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda *a: _lse_loss(*flash_attention_with_lse(*a, **kw)),
                 argnums=(0, 1, 2))(q, k, v)
    rg = jax.grad(lambda *a: _lse_loss(*_dense_with_lse(*a, causal, 512)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, rg, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_ring_merge_of_subtiled_blocks(rng, dtype):
    """Ring attention's arithmetic on one device: a query shard attends its
    own K/V block causally and an earlier block in full, and the two
    (out, lse) pairs merge by the blockwise identity; value and gradients
    (which reach both calls' lse cotangents) against dense causal attention
    over the whole sequence."""
    t = 512
    q, k, v = _rand_qkv(rng, 1, 2 * t, 2 * t, 2, 64, dtype)
    fwd, bwd = _tol(dtype)

    def merged(q, k, v):
        q2 = q[:, t:]
        oa, la = flash_attention_with_lse(q2, k[:, :t], v[:, :t],
                                          causal=False)
        ob, lb = flash_attention_with_lse(q2, k[:, t:], v[:, t:],
                                          causal=True)
        m = jnp.maximum(la, lb)
        wa, wb = jnp.exp(la - m)[..., None], jnp.exp(lb - m)[..., None]
        return (oa.astype(jnp.float32) * wa
                + ob.astype(jnp.float32) * wb) / (wa + wb)

    def dense(q, k, v):
        return scaled_dot_product_attention(q, k, v, causal=True)[:, t:]

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    np.testing.assert_allclose(merged(q, k, v), dense(*f32), **fwd)
    cot = jnp.asarray(rng.standard_normal((1, t, 2, 64)), jnp.float32)
    g = jax.grad(lambda *a: jnp.vdot(merged(*a), cot), (0, 1, 2))(q, k, v)
    rg = jax.grad(lambda *a: jnp.vdot(dense(*a), cot), (0, 1, 2))(*f32)
    for a, b, name in zip(g, rg, "qkv"):
        np.testing.assert_allclose(a.astype(np.float32), b, **bwd,
                                   err_msg=f"d{name}")


def test_tile_plan_at_the_training_shape():
    plan = fa.tile_plan(1024, 1024, True)
    sub = plan["sub_q"]
    assert plan["sub_k"] == sub == fa._SUB
    n = 1024 // sub
    assert plan["total"] == n * n
    assert plan["executed"] == n * (n + 1) // 2     # 10 of 16 at 256
    assert plan["masked"] == n                      # the diagonal's alone
    # visible pairs 1024 * 1025 / 2, so a hair under (n + 1) / n = 1.25
    assert plan["executed"] / plan["needed"] == pytest.approx(
        (n + 1) / n, rel=2e-3)
    full = fa.tile_plan(1024, 1024, False)
    assert full["executed"] == full["total"] and full["masked"] == 0
    assert full["executed"] / full["needed"] == 1.0


def _case_id(case):
    return "-".join(str(getattr(x, "__name__", x)) for x in case)


_PLANS = [
    # tq, tk, causal, block_q, block_k, dtype
    (1024, 1024, True, 1024, 1024, jnp.bfloat16),
    (1024, 1024, True, 1024, 1024, jnp.float32),    # 2 x 2 blocks of 512
    (2048, 2048, True, 1024, 1024, jnp.bfloat16),
    (2048, 2048, "offdiag", 1024, 1024, jnp.bfloat16),
    (1000, 1000, True, 1024, 1024, jnp.bfloat16),
    (1000, 1000, False, 1024, 1024, jnp.bfloat16),
    (1100, 1100, True, 1024, 1024, jnp.bfloat16),
    (320, 320, True, 1024, 1024, jnp.bfloat16),
    (100, 100, True, 1024, 1024, jnp.bfloat16),
    (96, 160, True, 1024, 1024, jnp.bfloat16),
    (640, 384, True, 1024, 1024, jnp.bfloat16),
    (900, 700, "offdiag", 512, 512, jnp.bfloat16),
    (1536, 1536, True, 512, 256, jnp.bfloat16),     # blocks that differ
    (1536, 1536, True, 256, 1024, jnp.bfloat16),
]


@pytest.mark.parametrize("case", _PLANS, ids=_case_id)
def test_tile_plan_counts_what_the_mask_leaves(case):
    """``tile_plan`` against the mask itself: inside the grid tiles that are
    live, a sub-tile is executed iff some (query, key) pair of it is visible,
    and masks iff some pair of it (padding included) is not."""
    tq, tk, causal, bq, bk, dtype = case
    plan = fa.tile_plan(tq, tk, causal, bq, bk, dtype)
    bq, bk = fa._clamp_blocks(dtype, tq, tk, bq, bk)
    sq, sk = plan["sub_q"], plan["sub_k"]
    assert bq % sq == 0 and bk % sk == 0
    tqp, tkp = -(-tq // bq) * bq, -(-tk // bk) * bk
    qpos, kpos = np.arange(tqp)[:, None], np.arange(tkp)[None, :]
    vis = np.broadcast_to(kpos < tk, (tqp, tkp)).copy()   # q padding computes
    if causal is True:
        vis &= kpos <= qpos
        live = kpos // bk * bk <= qpos // bq * bq + bq - 1
    elif causal == "offdiag":
        live = kpos // bk * bk + bk <= qpos // bq * bq
    else:
        live = np.ones((tqp, tkp), bool)
    vis &= live
    tiles = vis.reshape(tqp // sq, sq, tkp // sk, sk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    assert plan["executed"] == some.sum()
    assert plan["masked"] == (some & ~every).sum()
    assert plan["total"] == some.size
    needed = vis[:tq].sum() / (sq * sk)
    assert plan["needed"] == pytest.approx(needed)


@pytest.mark.parametrize("case", [
    (1024, 1024, True, jnp.bfloat16),   # one grid tile, one kind
    (1024, 1024, True, jnp.float32),    # 2 x 2 grid tiles of two kinds
    (1000, 1000, False, jnp.bfloat16),
    (640, 384, True, jnp.bfloat16),
    (16384, 128, False, jnp.float32),   # a head's dQ past the VMEM estimate
], ids=_case_id)
def test_kernels_execute_the_plan(rng, monkeypatch, case):
    """Count, in interpret mode, the sub-tiles whose scores each pass really
    computes: every piece of scores goes through ``_scores_t`` (a run of
    adjacent sub-tiles as one matmul: counted by its area), every piece that
    masks through ``_visible``.  The forward and each backward kernel run
    ``tile_plan``'s ``executed`` a head, ``masked`` of them with mask math:
    twice in all where ``backward_plan`` says one backward kernel, three
    times where it says the pair."""
    tq, tk, causal, dtype = case
    heads = 2
    plan = fa.tile_plan(tq, tk, causal, dtype=dtype)
    kernels = fa.backward_plan(tq, tk, 64, causal, dtype=dtype)["kernels"]
    assert kernels == (2 if tq == 16384 else 1)
    area = plan["sub_q"] * plan["sub_k"]
    counts = {"scores": 0, "masked": 0}

    def counting(name, fn, shape_of):
        def wrapped(*a, **kw):
            rows, cols = shape_of(*a)
            assert rows * cols % area == 0
            jax.debug.callback(lambda: counts.__setitem__(
                name, counts[name] + rows * cols // area))
            return fn(*a, **kw)
        return wrapped

    # the calls are jitted: drop the traces other tests left (they would run
    # without the counters) and, after, the ones made here
    def drop_traces():
        fa._fwd_call.clear_cache()
        fa._bwd_call.clear_cache()

    drop_traces()
    monkeypatch.setattr(fa, "_scores_t", counting(
        "scores", fa._scores_t, lambda k, q, _: (k.shape[0], q.shape[0])))
    monkeypatch.setattr(fa, "_visible", counting(
        "masked", fa._visible, lambda shape, *_, **__: shape))
    q, k, v = _rand_qkv(rng, 1, tq, tk, heads, 64, dtype)
    try:
        out, vjp = jax.vjp(lambda *a: flash_attention(*a, causal=causal),
                           q, k, v)
        jax.block_until_ready(out)
        jax.effects_barrier()
        assert counts == {"scores": heads * plan["executed"],
                          "masked": heads * plan["masked"]}
        jax.block_until_ready(vjp(jnp.ones_like(out)))
        jax.effects_barrier()
        # forward once, then the backward kernel, or dQ and dK/dV
        assert counts == {"scores": (1 + kernels) * heads * plan["executed"],
                          "masked": (1 + kernels) * heads * plan["masked"]}
        assert kernels * plan["executed"] == fa.backward_plan(
            tq, tk, 64, causal, dtype=dtype)["score_passes"]
    finally:
        drop_traces()


# -- one backward kernel (ISSUE 50) --------------------------------------------

def test_backward_plan_at_the_training_shape():
    """One backward kernel at the training cells' call (a head of 64 over
    1,024 positions in bfloat16): the 10 sub-tiles a causal pass executes
    are computed once for dQ, dK and dV, and a head's dQ takes 512 KB of
    VMEM (256 KB of float32 accumulator, a double-buffered block of 128 KB).
    Two kernels, each making the scores, where a head's dQ does not fit
    beside the dK/dV kernel's blocks."""
    plan = fa.backward_plan(1024, 1024, 64, True)
    assert plan == {"kernels": 1, "score_passes": 10,
                    "dq_resident_bytes": 512 << 10}
    assert plan["score_passes"] == fa.tile_plan(1024, 1024, True)["executed"]
    # chip_smoke.py's trainer, float32 operands, a head of 128
    assert fa.backward_plan(2048, 2048, 64, True)["kernels"] == 1
    assert fa.backward_plan(1024, 1024, 64, True,
                            dtype=jnp.float32)["kernels"] == 1
    assert fa.backward_plan(2048, 2048, 128, False)["kernels"] == 1
    # a very long sequence at a wide head
    long = fa.backward_plan(16384, 16384, 128, True)
    assert long["kernels"] == 2
    assert long["score_passes"] == 2 * fa.tile_plan(16384, 16384,
                                                    True)["executed"]
    assert long["dq_resident_bytes"] == 16384 * 128 * 8 > fa._VMEM_BUDGET


_BOTH_WAYS = [
    # tq, tk, d, causal, dtype, block, a cotangent for lse
    (1024, 1024, 64, True, jnp.bfloat16, 1024, False),   # the training call
    (1024, 1024, 64, False, jnp.bfloat16, 1024, True),
    (1024, 1024, 64, True, jnp.float32, 1024, False),    # 2 x 2 tiles of 512
    (1024, 1024, 64, "offdiag", jnp.float32, 512, True),
    (640, 384, 64, True, jnp.bfloat16, 1024, False),     # padded keys
    (640, 384, 64, False, jnp.float32, 1024, True),
    (1024, 1024, 128, True, jnp.bfloat16, 1024, True),   # a head of 128
    (2048, 2048, 64, True, jnp.bfloat16, 512, False),    # 4 x 4 tiles
    (2048, 2048, 64, "offdiag", jnp.bfloat16, 512, True),
]


@pytest.mark.parametrize("case", _BOTH_WAYS, ids=_case_id)
def test_one_backward_kernel_equals_the_pair(rng, monkeypatch, case):
    """The backward pass built BOTH ways on the same operands: the one
    kernel, which the shapes choose here, and the pair, which a call takes
    whose dQ does not fit VMEM.  dq, dk and dv are equal bit for bit: the
    same matmuls on the same operands, and where a head is several tiles
    each row of dq is summed over the k tiles in the same order, the pair's
    over its inner sweep, the one kernel's over its outer.  Both against the
    dense composition's gradients, with ``lse``'s cotangent live in half the
    cases."""
    tq, tk, d, causal, dtype, block, with_lse = case
    q, k, v = _rand_qkv(rng, 1, tq, tk, 1, d, dtype)
    cot = jnp.asarray(rng.standard_normal((1, tq, 1, d)), jnp.float32)
    kw = dict(causal=causal, block_q=block, block_k=block)
    assert fa.backward_plan(tq, tk, d, causal, block, block,
                            dtype)["kernels"] == 1

    def loss(o, lse):
        o = o.astype(jnp.float32)
        return jnp.vdot(o, cot) + (_lse_loss(o, lse) if with_lse else 0.0)

    def grads():
        fa._bwd_call.clear_cache()
        return jax.grad(lambda *a: loss(*flash_attention_with_lse(*a, **kw)),
                        argnums=(0, 1, 2))(q, k, v)

    try:
        one = grads()
        monkeypatch.setattr(fa, "_one_kernel_fits", lambda *a: False)
        pair = grads()
    finally:
        fa._bwd_call.clear_cache()
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    block = fa._clamp_blocks(dtype, tq, tk, block, block)[0]
    dense = jax.grad(lambda *a: loss(*_dense_with_lse(*a, causal, block)),
                     argnums=(0, 1, 2))(*f32)
    _, tol = _tol(dtype)
    for a, b, ref, name in zip(one, pair, dense, "qkv"):
        assert a.dtype == dtype
        np.testing.assert_array_equal(a, b, err_msg=f"d{name}")
        np.testing.assert_allclose(a.astype(np.float32), ref, **tol,
                                   err_msg=f"d{name}")


# -- a value width of its own (ISSUE 39) --------------------------------------

def _dense_heads_first(q, k, v, sm_scale):
    """The dense composition over (H, T, D) operands, causal, in float32."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = jnp.einsum("htd,hsd->hts", q, k) * sm_scale
    t = s.shape[-1]
    s = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], s,
                  -jnp.inf)
    return jnp.einsum("hts,hsd->htd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t", [1024, 1536, 2048])   # one grid step, K's
@pytest.mark.parametrize("d, dv", [(64, 64), (192, 128), (128, 64)])
def test_forward_with_a_value_width_of_its_own(rng, d, dv, t, dtype):
    """The forward kernel takes ``dv`` from v: a latent layer's 192 / 128,
    a v narrower than a lane tile, and today's ``dv == d``, at an explicit
    scale that is not ``d ** -0.5``, over one grid step, K's padding (1536
    in blocks of 1024) and several tiles; heads first, the kernel's own
    order, and through the (T, H, D) wrapper."""
    scale = 0.1447
    q = jnp.asarray(rng.standard_normal((2, t, d)), dtype)
    k = jnp.asarray(rng.standard_normal((2, t, d)), dtype)
    v = jnp.asarray(rng.standard_normal((2, t, dv)), dtype)
    out = fa.flash_attention_heads_first(q, k, v, causal=True, sm_scale=scale)
    assert out.shape == (2, t, dv) and out.dtype == dtype
    tol = (dict(atol=2e-5, rtol=2e-5) if dtype == jnp.float32
           else dict(atol=3e-2, rtol=3e-2))
    np.testing.assert_allclose(out.astype(np.float32),
                               _dense_heads_first(q, k, v, scale), **tol)
    swap = lambda a: jnp.swapaxes(a, 0, 1)
    np.testing.assert_array_equal(
        swap(flash_attention(swap(q), swap(k), swap(v), causal=True,
                             sm_scale=scale)), out)


def test_tile_plan_at_the_latent_buckets():
    """The sub-tiles a head executes at the two serving buckets, which the
    value width does not enter: 136 of 256 for 128.03 needed at 4,096 (six
    whole grid tiles of 16 and four diagonal ones of 10), 36 for 32.02 at
    2,048."""
    for t, executed, needed in ((4096, 136, 128.03), (2048, 36, 32.02)):
        plan = fa.tile_plan(t, t, True)
        assert plan["executed"] == executed and plan["sub_q"] == 256
        assert plan["needed"] == pytest.approx(needed, abs=5e-3)


def test_differentiating_a_value_width_of_its_own_says_why_not(rng):
    q = jnp.asarray(rng.standard_normal((1, 128, 1, 192)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 1, 128)), jnp.float32)
    loss = lambda q, k, v: flash_attention(q, k, v, causal=True).sum()
    with pytest.raises(NotImplementedError, match="forward kernel only"):
        jax.grad(loss, (0, 1, 2))(q, q, v)
    # the same call forward is fine, and dv == d still differentiates
    assert flash_attention(q, q, v, causal=True).shape == (1, 128, 1, 128)
    jax.grad(loss, (0, 1, 2))(q, q, q)
    with pytest.raises(ValueError, match="v's head size alone may differ"):
        flash_attention(q, v, v)


def _mosaic_kernels(monkeypatch, call, *args):
    """The Mosaic modules a jitted ``call`` lowers to for a TPU (no chip, no
    compile), as text without source locations."""
    import jax._src.tpu_custom_call as tcc
    seen, inner = [], tcc._lower_mosaic_module_to_asm
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    monkeypatch.setattr(
        tcc, "_lower_mosaic_module_to_asm", lambda module, **kw: (
            seen.append(module.operation.get_asm(enable_debug_info=False)),
            inner(module, **kw))[1])
    call.trace(*args).lower(lowering_platforms=("tpu",))
    return seen


def _mosaic_kernel(monkeypatch, *operands):
    """The Mosaic module ``_fwd_call`` lowers to."""
    (text,) = _mosaic_kernels(monkeypatch, fa._fwd_call, *operands, True,
                              0.125, 1024, 1024)
    return text


def _vmem(shape, dtype):
    return (f"memref<{'x'.join(map(str, shape))}x{dtype}, "
            f"#tpu.memory_space<vmem>>")


def test_the_training_call_lowers_to_the_blocks_it_always_had(monkeypatch):
    """``dv == d`` at the training cells' shape (128 heads of 64 over 1,024
    positions): q, k, v and the output in blocks of (1, 1024, 64), the
    statistics as rows, a (1024, 64) accumulator, one grid step a head.
    (The module's text, locations aside, is the parent commit's character
    for character: PERF.md section 6, PR 39.)  And at 192 / 128 only V's
    block, the accumulator and the output take the value width."""
    sds = lambda bh, t, w: jax.ShapeDtypeStruct((bh, t, w), jnp.bfloat16)
    text = _mosaic_kernel(monkeypatch, *[sds(128, 1024, 64)] * 3)
    main = next(l for l in text.splitlines() if "func.func @main" in l)
    block, row = _vmem((1, 1024, 64), "bf16"), _vmem((1, 1024), "f32")
    assert main.count(block) == 4
    assert main.count(row) == 2 and _vmem((1, 1, 1024), "f32") in main
    assert main.count(_vmem((1024, 64), "f32")) == 1
    assert "iteration_bounds = array<i64: 128, 1, 1>" in main
    fa._fwd_call.clear_cache()
    text = _mosaic_kernel(monkeypatch, sds(32, 4096, 192), sds(32, 4096, 192),
                          sds(32, 4096, 128))
    main = next(l for l in text.splitlines() if "func.func @main" in l)
    assert main.count(_vmem((1, 1024, 192), "bf16")) == 2
    assert main.count(_vmem((1, 1024, 128), "bf16")) == 2
    assert main.count(_vmem((1024, 128), "f32")) == 1
    assert "iteration_bounds = array<i64: 32, 4, 4>" in main
    fa._fwd_call.clear_cache()      # leave no TPU-lowered trace behind


def test_the_training_backward_lowers_to_one_kernel(monkeypatch):
    """The backward pass at the training cells' shape is ONE Mosaic kernel,
    one grid step a head: q, k, v and dO come in and dq, dk and dv go out in
    blocks of (1, 1024, 64), lse and delta as rows, and three (1024, 64)
    float32 accumulators stay in VMEM."""
    sds = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype)
    x, stat = sds(128, 1024, 64), sds(128, 1024, 1, dtype=jnp.float32)
    fa._bwd_call.clear_cache()
    try:
        (text,) = _mosaic_kernels(monkeypatch, fa._bwd_call, x, x, x, x, stat,
                                  x, True, 0.125, 1024, 1024)
    finally:
        fa._bwd_call.clear_cache()      # leave no TPU-lowered trace behind
    main = next(l for l in text.splitlines() if "func.func @main" in l)
    assert "iteration_bounds = array<i64: 128, 1, 1>" in main
    assert main.count(_vmem((1, 1024, 64), "bf16")) == 4 + 3
    assert main.count(_vmem((1, 1, 1024), "f32")) == 2
    assert main.count(_vmem((1024, 64), "f32")) == 3

"""The slot cache's format has one owner, ``tpu_dist/nn/cache.py`` (ISSUE 27):
resident leaves with time last; a call's ``index`` and ``valid`` mask and
the routed-row counters travel beside them, put in and taken out by
``call_state`` / ``split_state`` alone.  Each test fails, or cannot be
written, on the tree before it.  Since ISSUE 30 a cache holds leaves of two
kinds, time-indexed (``k``, ``v``, the int8 scales) and a slot's whole state
(Gated DeltaNet's ``state`` and ``conv``): every function there is run on
both below, and the host-side movers that cut along time refuse the second
kind by the leaf's name.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn, serve
from tpu_dist.models import Qwen3NextLM, TransformerLM

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dense(**over):
    return TransformerLM(**dict(dict(vocab_size=97, dim=32, depth=2,
                                     num_heads=4, max_seq_len=64), **over))


def _routed():
    """tests/test_olmoe.py's block: 8 gated experts of 32, 2 a token."""
    return _dense(vocab_size=211, dim=64, max_seq_len=128, num_experts=8,
                  moe_top_k=2, moe_hidden=32, moe_normalize_gates=False,
                  norm_eps=1e-5, norm="rmsnorm", rope=True, qk_norm=True,
                  attn_bias=False, moe_gated=True, moe_dispatch="dropless")


def _hybrid(**over):
    """tests/test_qwen3_next.py's block, one period: three Gated DeltaNet
    layers (state leaves) and one grouped-query attention layer (K/V)."""
    return Qwen3NextLM(**dict(dict(
        vocab_size=97, dim=32, depth=4, num_heads=4, num_kv_heads=1,
        head_dim=16, linear_key_heads=2, linear_value_heads=4,
        linear_key_dim=8, linear_value_dim=8, num_experts=8, moe_top_k=2,
        moe_hidden=16, shared_hidden=16, max_seq_len=64), **over))


def _filled(tree, seed=0):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: jnp.asarray(rng.integers(-100, 100, a.shape), a.dtype),
        tree)


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _names(tree):
    return {name for entry in tree.values() for name in entry}


# -- the call -----------------------------------------------------------------

@pytest.mark.parametrize("kind", ["float", "int8-with-scales",
                                  "with-counters"])
@pytest.mark.parametrize("index", ["scalar", "vector"])
def test_call_state_and_split_state_round_trip_a_pool(kind, index):
    model = _routed() if kind == "with-counters" else _dense()
    dtype = jnp.int8 if kind == "int8-with-scales" else jnp.bfloat16
    pool = _filled(model.init_slot_cache(3, 16, dtype))
    counters = _filled(model.init_moe_counters(), 1) or None
    assert _names(pool) == ({"k", "v", "k_scale", "v_scale"}
                            if kind == "int8-with-scales" else {"k", "v"})
    idx = (jnp.asarray(5, jnp.int32) if index == "scalar"
           else jnp.asarray([0, 5, 9], jnp.int32))
    valid = jnp.asarray([[False], [True], [True]])

    state = nn.cache.call_state(pool, idx, counters, valid)
    assert set(state) == set(pool) | set(counters or {})
    for path in pool:
        assert set(state[path]) == set(pool[path]) | {"index", "valid"}
        assert state[path]["index"] is idx
        assert state[path]["valid"] is valid
    for path in counters or {}:
        assert set(state[path]) == set(counters[path]) | {"valid"}

    # a layer's side publication (an aux loss) is part of neither tree
    state["block9.mlp"] = {"aux_loss": jnp.zeros(())}
    got_pool, got_counters = nn.cache.split_state(state, counters)
    _assert_trees_equal(got_pool, pool)
    _assert_trees_equal(got_counters, counters or {})
    assert not {"index", "valid"} & (_names(got_pool) | _names(got_counters))


def test_the_offline_state_is_the_slot_cache_addressed_at_zero():
    """``init_cache`` stores no write position of its own: it is
    ``init_slot_cache`` through the call builder."""
    model = _dense()
    attn = model.block0.attn
    assert set(attn.init_cache(2, 16)) == {"k", "v"}
    assert set(attn.init_cache(2, 16, jnp.int8)) == {"k", "v", "k_scale",
                                                     "v_scale"}
    state = model.init_cache(2, 16)
    assert all(e["index"].shape == () and int(e["index"]) == 0
               for e in state.values())
    pool, none = nn.cache.split_state(state)
    _assert_trees_equal(pool, model.init_slot_cache(2, 16))
    assert none == {}
    params = model.init(jax.random.key(0))
    _, after = model.apply(params, jnp.zeros((2, 5), jnp.int32), state=state)
    assert all(int(e["index"]) == 5 for e in after.values())


# -- the layout ---------------------------------------------------------------

def _rows(dtype, length, seed=0):
    """Two layers' batch-1 rows: 4-D K/V and, for int8, 3-D scales."""
    rng = np.random.default_rng(seed)
    entry = lambda: {"k": rng.integers(-9, 9, (1, 4, 8, length)).astype(dtype),
                     "v": rng.integers(-9, 9, (1, 4, 8, length)).astype(dtype)}
    rows = {"block0.attn": entry(), "block1.attn": entry()}
    if dtype == np.int8:
        for e in rows.values():
            e["k_scale"] = rng.random((1, 4, length)).astype(np.float32)
            e["v_scale"] = rng.random((1, 4, length)).astype(np.float32)
    return rows


KINDS = pytest.mark.parametrize("dtype", [np.float32, np.int8],
                                ids=["kv-4d", "kv-4d+scales-3d"])


@KINDS
def test_pad_time_against_numpy(dtype):
    rows = _rows(dtype, 11)
    padded = nn.cache.pad_time(rows, 16)
    for path, entry in rows.items():
        for name, leaf in entry.items():
            got = padded[path][name]
            assert got.shape == leaf.shape[:-1] + (16,)
            assert got.dtype == leaf.dtype
            np.testing.assert_array_equal(got[..., :11], leaf)
            assert not got[..., 11:].any()
    assert nn.cache.extent(padded) == (16, np.dtype(dtype))


@KINDS
def test_cut_and_join_along_time_against_numpy(dtype):
    rows = _rows(dtype, 12)
    blocks = [{path: {name: nn.cache.time_slice(leaf, lo, lo + 4)
                      for name, leaf in entry.items()}
               for path, entry in rows.items()} for lo in (0, 4, 8)]
    for name, leaf in rows["block1.attn"].items():
        np.testing.assert_array_equal(blocks[1]["block1.attn"][name],
                                      leaf[..., 4:8])
        assert leaf.shape[nn.cache.time_axis(leaf)] == 12
    _assert_trees_equal(nn.cache.join_time(blocks), rows)
    _assert_trees_equal(nn.cache.join_time(blocks[:1]), blocks[0])


@KINDS
def test_token_template_is_each_leaf_less_batch_and_time(dtype):
    want = {"k": ((4, 8), np.dtype(dtype)), "v": ((4, 8), np.dtype(dtype))}
    if dtype == np.int8:
        want.update(k_scale=((4,), np.dtype(np.float32)),
                    v_scale=((4,), np.dtype(np.float32)))
    assert nn.cache.token_template(_rows(dtype, 7)) == {
        "block0.attn": want, "block1.attn": want}
    # a pool of the same model describes the same tokens
    pool = _dense().init_slot_cache(
        5, 32, jnp.int8 if dtype == np.int8 else jnp.float32)
    assert (nn.cache.token_template(pool)
            == serve.kv_template(_rows(dtype, 9)))


# -- the slot write and the one prefill forward -------------------------------

@KINDS
def test_write_slot_rows_touches_one_slot_from_column_zero(dtype):
    cache_dtype = jnp.int8 if dtype == np.int8 else jnp.float32
    pool = _filled(_dense().init_slot_cache(3, 16, cache_dtype))
    rows = _rows(dtype, 8, seed=3)        # a bucket narrower than the pool
    got = nn.cache.write_slot_rows(pool, rows, 1)
    for path, entry in pool.items():
        for name, leaf in entry.items():
            want = np.array(leaf)
            want[1, ..., :8] = rows[path][name][0]
            np.testing.assert_array_equal(np.asarray(got[path][name]), want)


@pytest.mark.parametrize("kind", ["dense", "routed"])
def test_prefill_into_slot_is_prefill_rows_then_the_slot_write(kind):
    """Bitwise, the counters included: there is one prefill forward."""
    model = _routed() if kind == "routed" else _dense()
    params = model.init(jax.random.key(3))
    pool = _filled(model.init_slot_cache(3, 64, jnp.float32))
    counters = _filled(model.init_moe_counters(), 1)
    assert bool(counters) == (kind == "routed")
    prompt = np.zeros(16, np.int32)
    prompt[:11] = np.random.default_rng(4).integers(1, 97, 11)

    logits, new_pool, new_counters = jax.jit(model.prefill_into_slot)(
        params, prompt, 11, 2, pool, counters)
    # the rows hold the prompt's padded columns, not the pool's 64: they
    # land at column 0 of the slot and the rest of it stays (PR 48)
    row, rows, counted = jax.jit(
        lambda p, t, n, c: model.prefill_rows(p, t, n, 16, counters=c))(
            params, prompt, 11, counters)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(row))
    _assert_trees_equal(new_pool, nn.cache.write_slot_rows(pool, rows, 2))
    _assert_trees_equal(new_counters, counted)
    assert nn.cache.extent(rows) == (16, nn.cache.extent(pool)[1])
    if kind == "routed":
        c = new_counters["block1.mlp"]
        base = counters["block1.mlp"]
        assert int((c["rows"] - base["rows"]).sum()) == 11 * 2
        assert int(c["pad_rows"] - base["pad_rows"]) == 5 * 2
        assert int(c["calls"] - base["calls"]) == 1


@pytest.mark.parametrize("kind", ["dense", "routed"])
def test_engine_programs_take_counters_beside_the_pool_they_donate(kind):
    """``(params, cache, counters, ...)``: the pool is donated, the counters
    (``stats()`` reads them from another thread) are not."""
    model = _routed() if kind == "routed" else _dense()
    engine = serve.SlotEngine(model, model.init(jax.random.key(0)),
                              num_slots=2, max_len=64)
    assert set(engine._moe) == {"prefill", "decode"}
    assert _names(engine.cache) == {"k", "v"}
    assert bool(engine._moe["decode"]) == (kind == "routed")
    lowered = engine._decode.lower(
        engine.params, engine.cache, engine._moe["decode"], engine._slots,
        engine.active, False)
    _, cache_info, counter_info, *_ = lowered.args_info[0]
    assert all(a.donated for a in jax.tree.leaves(cache_info))
    assert not any(a.donated for a in jax.tree.leaves(counter_info))
    out = []
    engine.admit(serve.Request(np.arange(1, 8), 3,
                               on_token=lambda r, t: out.append(t)))
    before = engine._moe["decode"]
    while not engine.idle():
        engine.step()
    assert len(out) == 3
    if kind == "routed":
        assert engine._moe["decode"] is not before
        assert engine.stats()["moe"]["by_phase"]["decode"]["calls"] == 2 * 2


# -- two kinds of leaves (ISSUE 30) -------------------------------------------

STATE = {"block0.attn.state", "block0.attn.conv", "block1.attn.state",
         "block1.attn.conv", "block2.attn.state", "block2.attn.conv"}


def _hybrid_rows(length, seed=0):
    """Batch-1 host rows of the hybrid model with ``length`` columns."""
    rows = jax.tree.map(np.asarray, _filled(
        _hybrid().init_slot_cache(1, length, jnp.float32), seed))
    return rows


def test_a_hybrid_pool_holds_both_kinds_and_says_which():
    pool = _hybrid().init_slot_cache(3, 32, jnp.bfloat16)
    assert _names(pool) == {"k", "v", "state", "conv"}
    assert [nn.cache.is_timed(n) for n in ("k", "v", "k_scale", "v_scale",
                                           "state", "conv")] == [
        True, True, True, True, False, False]
    assert set(nn.cache.state_leaves(pool)) == STATE
    assert nn.cache.state_leaves(_dense().init_slot_cache(2, 16)) == []
    assert nn.cache.kv_entries(pool) == [pool["block3.attn"]]
    # the K/V pool is (B, Hkv, D, Tmax) in the cache type; the state is
    # float32 whatever the cache type, the tail flat and in the cache type
    assert pool["block3.attn"]["k"].shape == (3, 1, 16, 32)
    assert pool["block0.attn"]["state"].shape == (3, 4, 8, 8)
    assert pool["block0.attn"]["state"].dtype == jnp.float32
    assert pool["block0.attn"]["conv"].shape == (3, 3 * 64)
    assert pool["block0.attn"]["conv"].dtype == jnp.bfloat16
    # extent reads the K/V pool, wherever in the tree it is
    assert nn.cache.extent(pool) == (32, jnp.bfloat16)
    with pytest.raises(ValueError, match=r"holds\s+none"):
        nn.cache.extent({"block0.attn": pool["block0.attn"]})
    # bytes a slot holds whole, and bytes a position holds
    state, per_pos = nn.cache.slot_bytes(pool)
    assert state == 3 * (4 * 8 * 8 * 4 + 3 * 64 * 2)
    assert per_pos == 2 * 1 * 16 * 2
    assert nn.cache.slot_bytes(_dense().init_slot_cache(2, 16)) == (
        0, 2 * 2 * 32 * 4)


def test_call_and_split_round_trip_a_hybrid_pool():
    model = _hybrid()
    pool = _filled(model.init_slot_cache(3, 16, jnp.float32))
    counters = _filled(model.init_moe_counters(), 1)
    idx = jnp.asarray([0, 5, 9], jnp.int32)
    valid = jnp.asarray([[False], [True], [True]])
    state = nn.cache.call_state(pool, idx, counters, valid)
    assert set(state["block0.attn"]) == {"state", "conv", "index", "valid"}
    assert set(state["block3.attn"]) == {"k", "v", "index", "valid"}
    got_pool, got_counters = nn.cache.split_state(state, counters)
    _assert_trees_equal(got_pool, pool)
    _assert_trees_equal(got_counters, counters)


def test_pad_time_passes_state_through_and_pads_the_rest():
    rows = _hybrid_rows(11)
    padded = nn.cache.pad_time(rows, 16)
    for path, entry in rows.items():
        for name, leaf in entry.items():
            got = padded[path][name]
            if nn.cache.is_timed(name):
                assert got.shape == leaf.shape[:-1] + (16,)
                np.testing.assert_array_equal(got[..., :11], leaf)
                assert not got[..., 11:].any()
            else:
                assert got is leaf


def test_token_template_describes_a_state_leaf_whole():
    t = nn.cache.token_template(_hybrid().init_slot_cache(5, 32))
    assert t["block3.attn"] == {"k": ((1, 16), np.dtype(np.float32)),
                                "v": ((1, 16), np.dtype(np.float32))}
    assert t["block0.attn"] == {"state": ((4, 8, 8), np.dtype(np.float32)),
                                "conv": ((192,), np.dtype(np.float32))}


def test_write_slot_rows_lands_state_entire_and_kv_from_column_zero():
    pool = _filled(_hybrid().init_slot_cache(3, 16, jnp.float32))
    rows = _hybrid_rows(8, seed=3)        # a bucket narrower than the pool
    got = nn.cache.write_slot_rows(pool, rows, 1)
    for path, entry in pool.items():
        for name, leaf in entry.items():
            want = np.array(leaf)
            if nn.cache.is_timed(name):
                want[1, ..., :8] = rows[path][name][0]
            else:
                want[1] = rows[path][name][0]
            np.testing.assert_array_equal(np.asarray(got[path][name]), want)


def test_prefill_into_slot_of_a_hybrid_is_prefill_rows_then_the_write():
    model = _hybrid()
    params = model.init(jax.random.key(3))
    pool = _filled(model.init_slot_cache(3, 64, jnp.float32))
    counters = model.init_moe_counters()
    prompt = np.zeros(16, np.int32)
    prompt[:11] = np.random.default_rng(4).integers(1, 97, 11)
    logits, new_pool, _ = jax.jit(model.prefill_into_slot)(
        params, prompt, 11, 2, pool, counters)
    row, rows, _ = jax.jit(
        lambda p, t, n, c: model.prefill_rows(p, t, n, 16, counters=c))(
            params, prompt, 11, counters)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(row))
    _assert_trees_equal(new_pool, nn.cache.write_slot_rows(pool, rows, 2))


def test_the_engine_mirrors_a_hybrid_pool_without_a_branch():
    """``SlotEngine`` asks nn/cache.py and the model, never a leaf's name:
    the state counters come from ``slot_bytes``, the kernel flag from the
    model (a CPU run stays dense)."""
    model = _hybrid()
    engine = serve.SlotEngine(model, model.init(jax.random.key(0)),
                              num_slots=2, max_len=64)
    assert _names(engine.cache) == {"k", "v", "state", "conv"}
    assert engine.stats()["decode_attn"]["kernel"] is False
    out = []
    engine.admit(serve.Request(np.arange(1, 8), 3,
                               on_token=lambda r, t: out.append(t)))
    while not engine.idle():
        engine.step()
    assert len(out) == 3
    st = engine.stats()["state"]
    state, per_pos = nn.cache.slot_bytes(engine.cache)
    # two decode steps over one busy slot holding 7 then 8 positions
    assert st == {"state_bytes": 2 * 2 * state,
                  "kv_bytes": per_pos * (8 + 9),
                  "steps": 2, "kernel_steps": 0}            # a CPU run
    engine.reset_stats()
    assert engine.stats()["state"] == {"state_bytes": 0, "kv_bytes": 0,
                                       "steps": 0, "kernel_steps": 0}


def _raises_naming_a_state_leaf():
    return pytest.raises(NotImplementedError,
                         match=r"block0\.attn\.(state|conv).*no time axis")


def test_join_time_and_the_prefix_cache_refuse_state_leaves_by_name():
    blocks = [_hybrid_rows(4, seed) for seed in (0, 1)]
    with _raises_naming_a_state_leaf():
        nn.cache.join_time(blocks)
    cache = serve.PrefixCache(block_tokens=4)
    with _raises_naming_a_state_leaf():
        cache.insert(np.arange(8), _hybrid_rows(8), 8)
    with pytest.raises(NotImplementedError, match="PrefixCache.insert"):
        cache.insert(np.arange(8), _hybrid_rows(8), 8)


def test_kv_transfer_refuses_state_leaves_by_name():
    template = serve.kv_template(_hybrid().init_slot_cache(1, 16))
    with _raises_naming_a_state_leaf():
        serve.KVTransfer(None, template)


def test_the_disaggregated_engine_refuses_state_leaves_by_name():
    from tpu_dist.serve.disagg import DisaggSlotEngine
    model = _hybrid()
    with _raises_naming_a_state_leaf():
        DisaggSlotEngine(model, model.init(jax.random.key(0)), kv=None,
                         dispatch_ch=None, arrive_ch=None, num_slots=2,
                         max_len=32, rank=0)


def test_sharded_serving_refuses_state_leaves_by_name():
    with _raises_naming_a_state_leaf():
        serve.ShardedLM(_hybrid(), 0, 2)


# -- nobody else spells the format --------------------------------------------

def test_the_formats_names_stay_in_nn():
    """``"index"``, ``"valid"`` or ``"k" in entry`` anywhere else means a
    module builds or filters the call's state by hand again."""
    owners = {os.path.join("tpu_dist", "nn", f)
              for f in ("cache.py", "attention.py", "deltanet.py",
                        "mla.py", "moe.py", "shortconv.py")}
    pattern = re.compile(r'"index"|"valid"|"k" (not )?in ')
    found = []
    for folder, _, files in os.walk(os.path.join(ROOT, "tpu_dist")):
        for f in files:
            path = os.path.relpath(os.path.join(folder, f), ROOT)
            if f.endswith(".py") and path not in owners:
                with open(os.path.join(ROOT, path)) as fh:
                    found += [f"{path}:{i}: {line.strip()}"
                              for i, line in enumerate(fh, 1)
                              if pattern.search(line)]
    assert not found, "\n".join(found)
    for gone in ("cache_time_axis", "cache_time_slice"):
        assert not hasattr(nn, gone)

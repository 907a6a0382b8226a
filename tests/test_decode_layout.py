"""The K/V slot pool is stored the way the decode step uses it.

The mechanism's counter (ISSUE 24): the two pool programs are compiled HERE,
on the CPU box, for a DESCRIBED v5e at the serving cell's shapes (width 1600,
25 heads of 64, 32 slots x 1024, cache donated), and the optimized HLO must
hold no whole-pool ``copy`` — the relayout that cost 139 ms of every 178 ms
decode step while the pool was stored ``(B, Tmax, H, D)`` and written by a
native scatter (PERF.md, PR 24).  XLA's choice between a scatter that wants
its own layout and an in-place update is a heuristic; a later jaxlib or an
innocent edit can bring the copies back with every CPU test still green.

A compile is not a chip run and says nothing about time.  Where no v5e
topology can be described the compile cases skip, they never fail.  The
topology is described inside a fixture and only in this file (one process at
a time may load libtpu; see the on-chip-measurement guide, section 2).
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import nn
from tpu_dist.models import TransformerLM
from tpu_dist.serve.engine import (gathered_tables, pool_programs,
                                   prefill_width, row_major)

SLOTS, MAX_LEN, DIM, HEADS, DEPTH = 32, 1024, 1600, 25, 2
# The cell's vocabulary is 50257; the K/V path does not see it, and the older
# cases keep it cut for their speed.  The cases at the end of the file
# (ISSUE 31) compile the whole table, placed as the engine places it.
VOCAB = 2048
POOL_ELEMENTS = SLOTS * MAX_LEN * DIM
# Temporaries: the decode step held 272 MB with the relayout copies (two
# layers); one padded pool copy alone is 256 MiB.  The 1024-token prefill
# keeps its 25 x 1024 x 1024 scores, 100 MiB in float32 with the int8 cache
# (on the parent too), so its limit sits above that and below a pool copy.
TEMP_LIMIT = {"decode_step": 64 << 20, "prefill_into_slot": 128 << 20}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without a chip: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _param_shapes(model, sharding):
    """``model``'s parameters as served, bf16, as shapes on ``sharding``."""
    return _shapes(jax.eval_shape(
        lambda: jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                       model.init(jax.random.key(0)))),
        sharding)


def _compile(program, cache_dtype, sharding):
    """The optimized executable of ``decode_step`` or ``prefill_into_slot``
    over a donated pool, bf16 parameters, for the described chip."""
    model = TransformerLM(VOCAB, dim=DIM, depth=DEPTH, num_heads=HEADS,
                          max_seq_len=MAX_LEN)
    params = _param_shapes(model, sharding)
    cache = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(SLOTS, MAX_LEN, cache_dtype)),
        sharding)

    return _lower(model, program, params, cache, {}, sharding).compile()


def _lower(model, program, params, pool, counters, sharding, slots=SLOTS,
           bucket=MAX_LEN):
    """``serve.engine.pool_programs(model)`` — the program AS SERVED, the
    slot state and the live mask beside the donated pool (ISSUE 29) —
    lowered for the described chip; ``slots`` of the pool, prompts of
    ``bucket`` positions, as many as the engine gives a program of that
    bucket over this pool (ISSUE 48: ``SlotEngine.prefill_width``)."""
    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    ints = lambda *shape: arr(jnp.int32, *shape)
    state = {"tokens": ints(slots), "lengths": ints(slots),
             "steps": ints(slots), "temps": arr(jnp.float32, slots),
             "keys": arr(jnp.uint32, slots, 2)}
    decode, prefill = pool_programs(model)
    if program == "decode_step":
        return jax.jit(decode, donate_argnums=1, static_argnums=5).lower(
            params, pool, counters, state, arr(jnp.bool_, slots), False)
    width = prefill_width(bucket, nn.cache.extent(pool)[0])
    return jax.jit(prefill, donate_argnums=1, static_argnums=9).lower(
        params, pool, counters, state, ints(width, bucket), ints(width),
        ints(width), arr(jnp.float32, width), arr(jnp.uint32, width, 2),
        False)


def _pool_sized_results(hlo_text, ops, elements=POOL_ELEMENTS):
    """Instructions of the named kinds, in any computation of the module,
    with a result (or a member of a tuple result) of at least ``elements``
    elements: the K/V pool's count, unless another is given."""
    found = []
    for line in hlo_text.splitlines():
        m = re.search(r"= (.*?) (%s)\(" % "|".join(ops), line)
        if m and any(math.prod(int(d) for d in dims.split(",")) >= elements
                     for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1))):
            found.append(line.strip()[:160])
    return found


def _pool_sized_copies(hlo_text):
    return _pool_sized_results(hlo_text, ("copy",))


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("program", ["decode_step", "prefill_into_slot"])
def test_pool_program_has_no_whole_pool_copy(one_chip, no_compile_cache,
                                             program, cache_dtype):
    compiled = _compile(program, cache_dtype, one_chip)
    copies = _pool_sized_copies(compiled.as_text())
    assert not copies, (
        f"{program} relayouts the K/V pool again ({len(copies)} whole-pool "
        f"copies): the stored layout or the write form no longer lets XLA "
        f"update the donated pool in place\n" + "\n".join(copies[:4]))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_LIMIT[program], (
        f"{program} holds {temp / 2**20:.0f} MiB of temporaries (limit "
        f"{TEMP_LIMIT[program] >> 20} MiB; one padded pool copy is 256 MiB)")


def test_copy_counter_sees_a_relayout():
    """The counter itself: it finds a pool-sized copy in HLO text shaped
    like the parent's, and ignores a weight-sized one."""
    text = "\n".join([
        "  %copy.26 = bf16[32,1024,25,64]{3,2,1,0:T(8,128)(2,1)} "
        "copy(%c__block0_attn____k__.1), sharding={replicated}",
        "  %copy.22 = bf16[1600,4800]{0,1:T(8,128)(2,1)S(1)} "
        "copy(%p__block0_attn____qkv_weight__.1)",
        "  %fusion.2 = bf16[32,25,64,1024]{3,2,1,0} fusion(%copy.26)"])
    assert len(_pool_sized_copies(text)) == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["float32", "bfloat16", "int8"])
def test_time_is_the_last_axis_of_every_cache_leaf(dtype):
    """One rule for every leaf, and the helpers host code slices by."""
    model = TransformerLM(64, dim=32, depth=1, num_heads=4, max_seq_len=16)
    (entry,) = model.init_slot_cache(3, 16, dtype).values()
    assert entry["k"].shape == entry["v"].shape == (3, 4, 8, 16)
    if dtype == jnp.int8:
        assert entry["k_scale"].shape == entry["v_scale"].shape == (3, 4, 16)
    for leaf in entry.values():
        assert leaf.shape[nn.cache.time_axis(leaf)] == 16
        host = np.arange(leaf.size).reshape(leaf.shape)
        cut = nn.cache.time_slice(host, 2, 7)
        assert cut.shape == leaf.shape[:-1] + (5,)
        np.testing.assert_array_equal(cut, host[..., 2:7])


# -- a routed model's pool programs (ISSUE 25) --------------------------------

@pytest.fixture
def mosaic_gmm(monkeypatch):
    """``ops/gmm.py`` asks ``jax.default_backend()`` whether to interpret its
    kernels, and sees the CPU here: steer it to the Mosaic lowering, in the
    test (``tpu_dist.ops.gmm`` the attribute is the function, so the module
    comes from ``sys.modules``)."""
    import sys
    import tpu_dist.ops.gmm  # noqa: F401
    monkeypatch.setattr(sys.modules["tpu_dist.ops.gmm"], "_use_interpret",
                        lambda: False)


@pytest.fixture
def mosaic_moe_combine(monkeypatch):
    """``ops/moe_combine.py`` the same (``mosaic_gmm``): the combine of a
    layer that holds a share of its experts, by its buffer's rows."""
    import sys
    import tpu_dist.ops.moe_combine  # noqa: F401
    module = sys.modules["tpu_dist.ops.moe_combine"]
    monkeypatch.setattr(module, "_use_interpret", lambda: False)
    module._call.clear_cache()
    yield
    module._call.clear_cache()      # leave no Mosaic-lowered trace behind


@pytest.mark.parametrize("program", ["decode_step", "prefill_into_slot"])
def test_olmoe_pool_program_compiles_with_its_grouped_matmuls(
        one_chip, no_compile_cache, mosaic_gmm, program):
    """OLMoE's block at the published widths (2048, 16 heads of 128, 64
    gated experts of 1024, 8 a token; one layer, vocabulary cut), 32 slots x
    1024, with the routed-row counters beside the pool: the chip's
    compiler takes the grouped-matmul kernels at 16 rows a block (decode: 4
    rows an expert) and at 128 (a 1024-token prefill), three calls a layer
    named by their routed rows, and copies neither the pool nor an expert
    tensor (interpreted, the kernels would copy every one: 256 MiB each)."""
    experts, width, top_k = 64, 1024, 8
    model = TransformerLM(VOCAB, dim=2048, depth=1, num_heads=16,
                          max_seq_len=MAX_LEN, norm="rmsnorm", rope=True,
                          norm_eps=1e-5, attn_bias=False, qk_norm=True,
                          num_experts=experts, moe_top_k=top_k,
                          moe_hidden=width, moe_gated=True,
                          moe_normalize_gates=False, moe_dispatch="dropless")
    params = _param_shapes(model, one_chip)
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(SLOTS, MAX_LEN, jnp.bfloat16)),
        one_chip)
    counters = _shapes(jax.eval_shape(model.init_moe_counters), one_chip)

    compiled = _lower(model, program, params, pool, counters,
                      one_chip).compile()
    routed = (SLOTS if program == "decode_step" else MAX_LEN) * top_k
    text = compiled.as_text()
    calls = re.findall(r"%(gmm_r\d+)[.\d]* = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert calls == [f"gmm_r{routed}"] * 3, calls
    big = min(SLOTS * MAX_LEN * 2048, experts * 2048 * width)
    copies = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) >= big:
            copies.append(line.strip()[:160])
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


# -- the slot-decode kernel in the decode program (ISSUE 26) ------------------

@pytest.fixture
def mosaic_decode_attention(monkeypatch):
    """As ``mosaic_gmm``: steer ``ops/decode_attention.py`` to the Mosaic
    lowering although ``jax.default_backend()`` says CPU here."""
    from tpu_dist.ops import decode_attention
    monkeypatch.setattr(decode_attention, "_use_interpret", lambda: False)


@pytest.mark.parametrize("heads, dim", [(25, 1600), (16, 2048), (5, 320)],
                         ids=["gpt2xl-25x64", "olmoe-16x128", "shard-5x64"])
def test_decode_step_on_the_kernel_writes_no_pool_sized_result(
        one_chip, no_compile_cache, mosaic_decode_attention, heads, dim):
    """``decode_step`` with the slot-decode kernel forced, compiled for the
    described chip at the serving cells' head shapes (and one shard's of
    ``serve/sharded.py``): one ``decode_attention`` call a layer, whose
    aliased pools are the ONLY pool-sized results: no fusion and no copy
    reads a whole pool tensor to write one (the dense branch's two
    multi-output fusions a layer did, PERF.md section 5), and temporaries
    stay under the decode limit."""
    model = TransformerLM(VOCAB, dim=dim, depth=DEPTH, num_heads=heads,
                          max_seq_len=MAX_LEN)
    params = _param_shapes(model, one_chip)
    cache = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(SLOTS, MAX_LEN, jnp.bfloat16)),
        one_chip)
    with nn.attention_impl("flash"):
        compiled = _lower(model, "decode_step", params, cache, {},
                          one_chip).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(decode_attention)[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == DEPTH, calls
    big = _pool_sized_results(text, ("fusion", "copy"))
    assert not big, "\n".join(big[:4])
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_LIMIT["decode_step"], temp


def test_result_counter_sees_the_dense_branchs_fusions():
    """The counter itself: the dense branch's multi-output fusion (PERF.md
    section 5) counts, a weight-sized one and the kernel's call do not."""
    text = "\n".join([
        "  %multiply_reduce_fusion = (bf16[32,25,1024]{2,1,0}, "
        "bf16[32,25,64,1024]{3,2,1,0}) fusion(%p.1, %p.2), kind=kLoop",
        "  %fusion.7 = bf16[1600,4800]{1,0} fusion(%p.3), kind=kLoop",
        "  %decode_attention.1 = (bf16[32,64,25]{2,1,0}, "
        "bf16[32,25,64,1024]{3,2,1,0}, bf16[32,25,64,1024]{3,2,1,0}) "
        "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\""])
    assert len(_pool_sized_results(text, ("fusion", "copy"))) == 1


# -- the gathered tables lie as the programs read them (ISSUE 31) -------------

def _placed_shapes(model, sharding):
    """bf16 parameter shapes of ``model`` for the described chip, each
    gathered table in the format ``serve.engine.place_params`` gives it."""
    params = _param_shapes(model, sharding)
    for path, name in gathered_tables(model):
        leaf = params[path][name]
        params[path][name] = jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=row_major(sharding))
    return params


@pytest.mark.parametrize("vocab, dim, heads",
                         [(50257, 1600, 25), (50304, 2048, 16)],
                         ids=["gpt2xl-50257x1600", "olmoe-50304x2048"])
@pytest.mark.parametrize("program", ["decode_step", "prefill_into_slot"])
def test_pool_program_reads_the_token_table_where_it_lies(
        one_chip, no_compile_cache, mosaic_decode_attention, program,
        vocab, dim, heads):
    """The pool programs as served (the slot kernel taken) over the WHOLE
    vocabulary, the parameters in the formats the engine's helper gives
    them: the entry parameter of ``tok.weight`` is row-major and no copy
    writes a table-sized result.  In the device's default format, which
    for ``bf16[50257, 1600]`` is vocabulary-minor (1600 is 12.5 tiles of
    128 lanes), both programs opened with a 160 MB copy of the table:
    0.51 ms of every 5.09 ms decode step (PERF.md, PR 31)."""
    model = TransformerLM(vocab, dim=dim, depth=DEPTH, num_heads=heads,
                          max_seq_len=MAX_LEN)
    params = _placed_shapes(model, one_chip)
    cache = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(SLOTS, MAX_LEN, jnp.bfloat16)),
        one_chip)
    with nn.attention_impl("flash"):
        compiled = _lower(model, program, params, cache, {},
                          one_chip).compile()
    table = compiled.input_formats[0][0]["tok"]["weight"]
    assert table.layout.major_to_minor == (0, 1), table
    copies = _pool_sized_results(compiled.as_text(), ("copy",), vocab * dim)
    assert not copies, (
        f"{program} copies the token table again at every call\n"
        + "\n".join(copies[:4]))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_LIMIT[program], (
        f"{program} holds {temp / 2**20:.0f} MiB of temporaries (limit "
        f"{TEMP_LIMIT[program] >> 20} MiB; the table is 153 MiB)")


def test_copy_counter_sees_a_table_sized_copy():
    """The counter itself, at the table's size: it finds a copy shaped
    like the parent's and ignores the compiler's prefetch of a weight."""
    text = "\n".join([
        "  %copy.23 = bf16[50257,1600]{1,0:T(8,128)(2,1)} "
        "copy(%p__tok____weight__.1), sharding={replicated}",
        "  %copy.22 = bf16[1600,4800]{0,1:T(8,128)(2,1)S(1)} "
        "copy(%p__block0_attn____qkv_weight__.1)",
        "  %gather = bf16[32,1600]{1,0} gather(%copy.23, %tokens)"])
    assert len(_pool_sized_results(text, ("copy",), 50257 * 1600)) == 1


# -- a latent pool's decode kernel (ISSUE 32) ---------------------------------

def test_latent_decode_kernel_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, mosaic_decode_attention):
    """``latent_decode_attention`` alone, for the described chip, at Kimi
    K2's serving shapes: 128 slots of 4,096 positions of a 576-wide
    bfloat16 latent, 64 query rows a slot, values the first 512 rows.  The
    chip's compiler takes the 576-deep and the transposed matmul, the
    aliased pool is not copied, and nothing pool-sized is a temporary."""
    from tpu_dist.ops.decode_attention import latent_decode_attention
    b, h, c, r, tmax = 128, 64, 576, 512, 4096
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    compiled = jax.jit(
        lambda q, new, pool, lengths: latent_decode_attention(
            q, new, pool, lengths, value_dim=r, scale=0.13),
        donate_argnums=2).lower(
        arr(jnp.bfloat16, b, h, c), arr(jnp.bfloat16, b, c),
        arr(jnp.bfloat16, b, c, tmax), arr(jnp.int32, b)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%latent_decode_attention[.\d]* = [^\n]*"
                          r"custom_call_target=\"tpu_custom_call\"",
                          text)) == 1
    assert not _pool_sized_results(text, ("fusion", "copy"), b * c * tmax)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def _kimi_k2_two_layers(sharding):
    """The DeepSeek-V3 block at the published widths, two layers (one dense,
    one that holds 12 of 384 experts), vocabulary cut: ``(model, params,
    pool, counters)`` as shapes on ``sharding``, 32 slots x 1024."""
    from tpu_dist.models import KimiK2LM
    model = KimiK2LM(
        VOCAB, dim=7168, depth=2, num_heads=64, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, dense_hidden=18432, num_experts=384, moe_top_k=8,
        moe_hidden=2048, routed_scaling_factor=2.827, experts_held=12,
        rope_scaling_factor=32, rope_scaling_beta_fast=1,
        rope_scaling_mscale_all_dim=1, max_seq_len=MAX_LEN)
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(SLOTS, MAX_LEN, jnp.bfloat16)),
        sharding)
    counters = _shapes(jax.eval_shape(model.init_moe_counters), sharding)
    return model, _param_shapes(model, sharding), pool, counters


def test_kimi_k2_decode_step_on_the_kernel_writes_no_pool_sized_result(
        one_chip, no_compile_cache, mosaic_gmm, mosaic_decode_attention):
    """The decode program of the DeepSeek-V3 block at the published widths
    (7168, 64 heads over a 512 + 64 latent, a dense layer of 18,432 and an
    expert layer that holds 12 of 384 experts of 2,048; two layers,
    vocabulary cut), 32 slots x 1024: one ``latent_decode_attention`` call
    a layer whose aliased pool is the ONLY pool-sized result (the dense
    branch selects the new column into the whole pool: a pool-sized fusion
    a layer), the grouped matmuls as Mosaic calls, and no expert tensor
    copied."""
    model, params, pool, counters = _kimi_k2_two_layers(one_chip)

    def pool_results():
        """Fusions and copies with a result of the pool's own shape (the
        weights here are larger than this small pool: not by size)."""
        text = _lower(model, "decode_step", params, pool, counters,
                      one_chip).compile().as_text()
        shape = re.escape(f"bf16[{SLOTS},576,{MAX_LEN}]")
        return text, [line.strip()[:160] for line in text.splitlines()
                      if re.search(r"= [^=]*%s[^=]* (fusion|copy)\(" % shape,
                                   line)]

    with nn.attention_impl("flash"):
        text, found = pool_results()
    calls = re.findall(r"%(latent_decode_attention|gmm_r\d+)[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    # (the expert layer's usual-size and worst-size branches: 3 calls each)
    assert sorted(calls) == sorted(["latent_decode_attention"] * 2
                                   + [f"gmm_r{SLOTS * 8}"] * 6), calls
    assert not found, found
    with nn.attention_impl("dense"):
        _, dense = pool_results()
    assert len(dense) >= 2, dense


# -- a latent layer's whole-prompt prefill on the flash forward kernel
#    (ISSUE 39) ----------------------------------------------------------------

@pytest.fixture
def mosaic_flash(monkeypatch):
    """``ops/flash_attention.py`` the same (``mosaic_gmm``)."""
    import sys
    import tpu_dist.ops.flash_attention  # noqa: F401
    fa = sys.modules["tpu_dist.ops.flash_attention"]
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    fa._fwd_call.clear_cache()
    fa._bwd_call.clear_cache()
    yield fa
    fa._fwd_call.clear_cache()      # leave no Mosaic-lowered trace behind
    fa._bwd_call.clear_cache()


def test_kimi_k2_prefill_on_the_kernel_holds_no_score_tensor(
        one_chip, no_compile_cache, mosaic_gmm, mosaic_flash,
        mosaic_moe_combine):
    """The prefill program of the same two layers at a 1,024 bucket: on the
    kernel branch the chip's compiler takes one ``flash_fwd`` call a layer
    at heads of 192 / 128 and the optimized program produces no array of
    shape ``[64, 1024, 1024]`` (the dense branch's holds the bfloat16
    scores, 128 MiB a layer, and 58 MiB of temporaries more: 228.8 against
    170.8 MiB)."""
    model, params, pool, counters = _kimi_k2_two_layers(one_chip)
    scores = re.compile(r"\w+\[64,%d,%d\]" % (MAX_LEN, MAX_LEN))
    seen = {}
    for impl in ("flash", "dense"):
        with nn.attention_impl(impl):
            compiled = _lower(model, "prefill_into_slot", params, pool,
                              counters, one_chip).compile()
        text = compiled.as_text()
        seen[impl] = (
            len(re.findall(r"%flash_fwd[.\d]* = [^\n]*"
                           r"custom_call_target=\"tpu_custom_call\"", text)),
            sorted(set(scores.findall(text))),
            compiled.memory_analysis().temp_size_in_bytes)
    calls, score_shapes, temp = seen["flash"]
    assert calls == 2 and not score_shapes, (calls, score_shapes)
    calls, score_shapes, dense_temp = seen["dense"]
    assert calls == 0 and "bf16[64,1024,1024]" in score_shapes
    assert dense_temp - temp >= 48 << 20, (dense_temp, temp)


# -- a slot of whole state beside a headless latent (ISSUE 40) -----------------

@pytest.fixture
def mosaic_delta_step(monkeypatch):
    """As ``mosaic_gmm``: steer ``ops/delta_step.py`` to the Mosaic lowering
    although ``jax.default_backend()`` says CPU here."""
    import sys
    import tpu_dist.ops.delta_step  # noqa: F401
    module = sys.modules["tpu_dist.ops.delta_step"]
    monkeypatch.setattr(module, "_use_interpret", lambda: False)
    yield
    module._call.clear_cache()      # leave no Mosaic-lowered trace behind


@pytest.mark.parametrize("program", ["decode_step", "prefill_into_slot"])
def test_kimi_linear_pool_programs_compile_at_the_cells_shapes(
        one_chip, no_compile_cache, mosaic_gmm, mosaic_decode_attention,
        mosaic_delta_step, mosaic_moe_combine, program, capsys):
    """Kimi Linear's block at the published widths, two layers (a Kimi
    Delta Attention layer over the dense MLP, a latent layer without rank
    or rope over 32 held of 256 experts), vocabulary cut, at the cell's 120
    slots x 1,024 and its 256 bucket: the chip's compiler takes both pool
    programs, the decode step holds one ``latent_decode_attention`` call,
    one ``delta_step`` call and the grouped matmuls as Mosaic calls, and the
    252 MB float32 state is never copied (its update is in place in the
    donated pool).  The
    cell names 120 slots because THIS compile is refused at 128 (a
    ``bf16[1024,2304]`` gather of the expert layer's combine runs out of
    scoped vmem: PERF.md section 7)."""
    from tpu_dist.models import KimiLinearLM
    slots = 120
    model = KimiLinearLM(
        VOCAB, dim=2304, depth=2, num_heads=32, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        dense_hidden=9216, kda_layers=[1], full_attn_layers=[2],
        num_experts=256, moe_top_k=8, moe_hidden=1024,
        routed_scaling_factor=2.446, experts_held=32, max_seq_len=MAX_LEN)
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(slots, MAX_LEN, jnp.bfloat16)),
        one_chip)
    assert pool["block0.attn"]["state"].shape == (slots, 32, 128, 128)
    counters = _shapes(jax.eval_shape(model.init_moe_counters), one_chip)
    with nn.attention_impl("flash"):
        compiled = _lower(model, program, _param_shapes(model, one_chip),
                          pool, counters, one_chip, slots=slots,
                          bucket=256).compile()
    text = compiled.as_text()
    calls = re.findall(r"%(latent_decode_attention|gmm_r\d+)[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    # a prefill program of the 256 bucket takes four prompts (ISSUE 48):
    # 8,192 picks, from which the combine goes by the buffer's rows
    prompts = prefill_width(256, MAX_LEN)
    assert prompts == 4
    picks = (slots if program == "decode_step" else prompts * 256) * 8
    assert f"gmm_r{picks}" in calls, calls
    assert ("latent_decode_attention" in calls) == (program == "decode_step")
    state = re.escape(f"f32[{slots},32,128,128]")
    assert not [line for line in text.splitlines()
                if re.search(r"= [^=]*%s[^=]* copy\(" % state, line)]
    memory = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[kimi-linear, 2 of 14 layers, {slots} slots] {program}: "
              f"arguments {memory.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"temporaries {memory.temp_size_in_bytes / 2**20:.1f} MiB")
    assert memory.temp_size_in_bytes < 512 << 20
    if program == "decode_step":
        # ISSUE 41: the one-token update is ONE ``delta_step`` call fed the
        # donated pool's leaf itself, and nothing else in the program yields
        # an array of the state's shape (the ``jax.numpy`` form's
        # multiply-add fusion did): read once, written once, in place
        makers = re.findall(r"%([\w.-]+) = [^=\n]*?" + state
                            + r"[^=\n]*? ([\w-]+)\(", text)
        assert [(n.split(".")[0], op) for n, op in makers
                if op not in ("parameter", "get-tuple-element", "tuple",
                              "bitcast")] == [("delta_step", "custom-call")]
        assert re.search(r"%delta_step[.\d]* = [^\n]*custom-call\("
                         r"%cache__block0_attn____state__", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# -- a Gated DeltaNet prefill on the scan kernel (ISSUE 42) --------------------

@pytest.fixture
def mosaic_delta_scan(monkeypatch):
    """As ``mosaic_delta_step``, for ``ops/delta_scan.py``."""
    import sys
    import tpu_dist.ops.delta_scan  # noqa: F401
    module = sys.modules["tpu_dist.ops.delta_scan"]
    monkeypatch.setattr(module, "_use_interpret", lambda: False)
    module._call.clear_cache()
    yield
    module._call.clear_cache()      # leave no Mosaic-lowered trace behind


def test_qwen3_next_prefill_holds_one_delta_scan_call_a_recurrent_layer(
        one_chip, no_compile_cache, mosaic_gmm, mosaic_flash,
        mosaic_decode_attention, mosaic_delta_step, mosaic_delta_scan,
        mosaic_moe_combine):
    """Qwen3-Next's block at the published widths, one period of the layer
    pattern (three Gated DeltaNet layers of 16 key / 32 value heads of 128 x
    128, one gated full-attention layer; 64 held of 512 experts, vocabulary
    cut), at the cell's 96 slots x 4,096 and its 4,096 bucket: the chip's
    compiler takes the prefill program with ONE ``delta_scan`` call a
    recurrent layer (its state operand the zeros a request starts from, its
    result written into the slot's row of the donated pool), and on that
    branch nothing of the ``jax.numpy`` scan's shapes is left in the
    program: no ``(1, 32, 64, 64, 64)`` decay or power, no ``(1, 32, 64,
    64, 128)`` operand of a doubling step, no carry of 64 steps (the dense
    branch holds all three), and the program's temporaries are smaller."""
    from tpu_dist.models import Qwen3NextLM
    slots, max_len = 96, 4096
    model = Qwen3NextLM(
        VOCAB, dim=2048, depth=4, num_heads=16, num_kv_heads=2, head_dim=256,
        num_experts=512, experts_held=64, moe_top_k=10, moe_hidden=512,
        shared_hidden=512, max_seq_len=max_len)
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(slots, max_len, jnp.bfloat16)),
        one_chip)
    assert pool["block0.attn"]["state"].shape == (slots, 32, 128, 128)
    counters = _shapes(jax.eval_shape(model.init_moe_counters), one_chip)
    params = _param_shapes(model, one_chip)
    scan_shapes = re.compile(r"f32\[1,32,64,64,(?:64|128)\]")
    seen = {}
    for impl in ("flash", "dense"):
        with nn.attention_impl(impl):
            assert model.prefill_scan_kernel(pool, max_len) is (
                impl == "flash")
            compiled = _lower(model, "prefill_into_slot", params, pool,
                              counters, one_chip, slots=slots,
                              bucket=max_len).compile()
        text = compiled.as_text()
        seen[impl] = (
            len(re.findall(r"%delta_scan[.\d]* = [^\n]*"
                           r"custom_call_target=\"tpu_custom_call\"", text)),
            sorted(set(scan_shapes.findall(text))),
            compiled.memory_analysis().temp_size_in_bytes)
        # ISSUE 46: each of the four expert layers combines its usual-size
        # buffer by its rows, a ``moe_combine`` call the chip's compiler
        # takes over the half of the rows and one over all of them, beside
        # the grouped matmuls of both buffers
        kernels = re.findall(r"%(moe_combine|gmm_r\d+)[.\d]* = [^\n]*"
                             r"custom_call_target=\"tpu_custom_call\"", text)
        assert kernels.count("moe_combine") == 4 * 2, kernels
        assert kernels.count("gmm_r40960") == 4 * 2 * 3, kernels
    calls, shapes, temp = seen["flash"]
    assert calls == 3 and not shapes, (calls, shapes)
    calls, shapes, dense_temp = seen["dense"]
    assert calls == 0 and shapes == ["f32[1,32,64,64,128]",
                                    "f32[1,32,64,64,64]"]
    assert temp < dense_temp, (temp, dense_temp)


# -- K/V columns and a whole state in EVERY layer (ISSUE 44) --------------------

@pytest.mark.parametrize("program", ["decode_step", "prefill_into_slot"])
def test_falcon_h1_pool_programs_compile_at_the_cells_shapes(
        one_chip, no_compile_cache, mosaic_decode_attention, program, capsys):
    """Falcon-H1's layer at the published widths, two layers (each a
    grouped-query attention of 20 heads over 4 K/V heads of 128 AND a Mamba-2
    mixer of 32 heads of 128 over a state of 256, then the MLP of 21,504;
    every multiplier as published; vocabulary cut), at the cell's 80 slots x
    1,024 and its 256 bucket: the chip's compiler takes both pool programs
    and ``memory_analysis()`` is reported.  Since ISSUE 45 the decode step
    holds ONE ``decode_attention`` Mosaic call a layer (the grouped form:
    ``G`` = 5 read from the shapes), whose aliased pools are the only
    pool-sized results (no ``copy``, no ``select`` over ``bf16[80,4,128,
    1024]``: the dense branch rewrote the pool through one); the prefill
    holds none, and no other kernel is this model's (the state's update and
    scan are ``jax.numpy``).  The decode step's update of the 336 MB float32
    state of a layer is ONE fusion that reads the donated pool's leaf and
    yields the new state and the output together: read once, written once,
    in place, never copied."""
    from tpu_dist.models import FalconH1LM
    slots = 80
    model = FalconH1LM(
        VOCAB, dim=5120, depth=2, num_heads=20, num_kv_heads=4, head_dim=128,
        mlp_hidden=21504, mamba_heads=32, mamba_head_dim=128,
        mamba_state_dim=256, mamba_groups=2, embedding_multiplier=5.656854,
        lm_head_multiplier=0.0078125, attention_out_multiplier=0.0375,
        key_multiplier=0.0110485, ssm_in_multiplier=0.25,
        ssm_multipliers=(0.3535534, 0.25, 0.1767767, 0.5, 0.3535534),
        ssm_out_multiplier=0.0883883, mlp_multipliers=(0.1767767, 0.0111607),
        max_seq_len=MAX_LEN)
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(slots, MAX_LEN, jnp.bfloat16)),
        one_chip)
    assert pool["block1.attn.ssm"]["state"].shape == (slots, 32, 128, 256)
    assert pool["block1.attn.attention"]["k"].shape == (slots, 4, 128,
                                                        MAX_LEN)
    with nn.attention_impl("flash"):        # as a TPU backend would choose
        assert model.slot_decode_kernel(pool) is True
        assert model.slot_state_kernel(pool) is False
        assert model.prefill_scan_kernel(pool, 256) is False
        compiled = _lower(model, program, _param_shapes(model, one_chip),
                          pool, {}, one_chip, slots=slots,
                          bucket=256).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[falcon-h1, 2 of 9 layers, {slots} slots] {program}: "
              f"arguments {memory.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"temporaries {memory.temp_size_in_bytes / 2**20:.1f} MiB")
    calls = re.findall(r"%([\w-]+?)[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert calls == (["decode_attention"] * 2 if program == "decode_step"
                     else []), calls
    state = re.escape(f"f32[{slots},32,128,256]")
    assert not [line for line in text.splitlines()
                if re.search(r"= [^=]*%s[^=]* copy\(" % state, line)]
    assert not _pool_sized_results(text, ("copy", "select"),
                                   elements=slots * MAX_LEN * 4 * 128)
    assert memory.temp_size_in_bytes < 128 << 20
    if program == "decode_step":
        # whatever yields an array of the state's shape is a fusion that
        # yields the layer's output with it: one a layer
        updates = re.findall(r"= ([^=\n]*?%s[^=\n]*?) fusion\(([^\n]*?)\), "
                             r"kind=" % state, text)
        assert len(updates) == 2, updates
        for layer, (result, operands) in enumerate(updates):
            assert re.search(r"f32\[%d,32,128\]\{" % slots, result)
            assert f"%cache__block{layer}_attn_ssm____state__" in operands


def test_qwen3_next_decode_step_holds_one_grouped_call_a_full_attention_layer(
        one_chip, no_compile_cache, mosaic_gmm, mosaic_decode_attention,
        mosaic_delta_step, capsys):
    """The twin (ISSUE 45): Qwen3-Next's block at the published widths, one
    period of the layer pattern (three Gated DeltaNet layers, one gated
    full-attention layer of 16 query heads over 2 K/V heads of 256: ``G`` =
    8, ``D`` = 256; 64 held of 512 experts, vocabulary cut), at the cell's
    96 slots x 4,096: the chip's compiler takes the decode program with one
    ``decode_attention`` call for the full-attention layer beside the three
    ``delta_step`` calls, no pool-sized ``copy`` or ``select`` over the 2.4
    GB pool's leaves, and ``memory_analysis()`` is reported."""
    from tpu_dist.models import Qwen3NextLM
    slots, max_len = 96, 4096
    model = Qwen3NextLM(
        VOCAB, dim=2048, depth=4, num_heads=16, num_kv_heads=2, head_dim=256,
        num_experts=512, experts_held=64, moe_top_k=10, moe_hidden=512,
        shared_hidden=512, max_seq_len=max_len)
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(slots, max_len, jnp.bfloat16)),
        one_chip)
    assert pool["block3.attn"]["k"].shape == (slots, 2, 256, max_len)
    counters = _shapes(jax.eval_shape(model.init_moe_counters), one_chip)
    with nn.attention_impl("flash"):
        assert model.slot_decode_kernel(pool) is True
        compiled = _lower(model, "decode_step",
                          _param_shapes(model, one_chip), pool, counters,
                          one_chip, slots=slots, bucket=max_len).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[qwen3-next, 4 of 12 layers, {slots} slots] decode_step: "
              f"arguments {memory.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"temporaries {memory.temp_size_in_bytes / 2**20:.1f} MiB")
    calls = re.findall(r"%(decode_attention|delta_step)[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(calls) == ["decode_attention"] + ["delta_step"] * 3, calls
    assert not _pool_sized_results(text, ("copy", "select"),
                                   elements=slots * max_len * 2 * 256)
    assert memory.temp_size_in_bytes < 128 << 20


# -- the combine of a share of the experts, by its buffer's rows (ISSUE 46) ----

def _without_the_combine_by_rows(monkeypatch):
    """The expert layer as it was before ISSUE 46: every call combined by a
    row gather for every pick."""
    from tpu_dist.nn import moe
    monkeypatch.setattr(moe, "_combines_by_token", lambda m_rows, kn: False)


def test_qwen3_next_prefill_combines_its_usual_buffer_by_its_rows(
        one_chip, monkeypatch, mosaic_gmm, mosaic_flash, mosaic_delta_scan,
        mosaic_moe_combine):
    """The hybrid's ``prefill_into_slot`` lowered at the cell's shapes (96
    slots x 4,096, a 4,096 bucket: 40,960 picks a layer over 64 held of 512
    experts).  Each expert layer is a ``cond`` over two row buffers: the
    usual one (15,360 rows) is combined by its rows through the
    ``moe_combine`` kernel and holds no ``(10, 4096, 2048)`` array of every
    pick's row any more; the buffer of every pick keeps the row-gather form
    (tests/test_qwen3_next.py sends every pick and takes it).  So the
    program holds half the per-pick arrays it held, and every ``gmm_r40960``
    call it had."""
    from tpu_dist.models import Qwen3NextLM
    slots, max_len = 96, 4096
    model = Qwen3NextLM(
        VOCAB, dim=2048, depth=4, num_heads=16, num_kv_heads=2, head_dim=256,
        num_experts=512, experts_held=64, moe_top_k=10, moe_hidden=512,
        shared_hidden=512, max_seq_len=max_len)
    layer = model.block0.mlp
    assert layer._buffer_sizes(40960, layer._block_rows(
        40960, jnp.bfloat16)) == [15360, 46080]
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(slots, max_len, jnp.bfloat16)),
        one_chip)
    counters = _shapes(jax.eval_shape(model.init_moe_counters), one_chip)
    params = _param_shapes(model, one_chip)

    def facts():
        with nn.attention_impl("flash"):
            text = _lower(model, "prefill_into_slot", params, pool, counters,
                          one_chip, slots=slots, bucket=max_len).as_text()
        return (len(re.findall(r"tensor<10x4096x2048xbf16>", text)),
                len(re.findall(r'kernel_name = "gmm_r40960"', text)),
                len(re.findall(r'kernel_name = "moe_combine"', text)))

    per_pick, gmm, kernel = facts()
    _without_the_combine_by_rows(monkeypatch)
    was_per_pick, was_gmm, was_kernel = facts()
    # four layers x two buffers x three grouped matmuls, on both sides
    assert gmm == was_gmm == 24
    # jitted: one body for the four layers' calls over half the buffer's
    # rows (the held picks of most calls fit those) and one over all
    assert (kernel, was_kernel) == (2, 0)
    # the same arrays fewer in each of the four layers (what is left is the
    # other buffer's branch and the router's own (k, N) bookkeeping)
    gone = was_per_pick - per_pick
    assert gone > 0 and gone % 4 == 0, (per_pick, was_per_pick)


@pytest.mark.parametrize("cell", ["olmoe", "xing4"])
@pytest.mark.parametrize("program", ["decode_step", "prefill_into_slot"])
def test_a_model_that_holds_every_expert_lowers_as_it_did(
        one_chip, monkeypatch, mosaic_gmm, mosaic_flash,
        mosaic_decode_attention, mosaic_moe_combine, program, cell):
    """OLMoE's block (64 of 64 experts, 8 a token, 32 slots x 1,024) and
    Xing4.0's (64 of 64, 4 a token, four residual streams; one dense and
    one expert layer of the cell's configuration, 96 slots x 4,096): a
    buffer that holds every pick is never at most half the picks, so both
    pool programs lower to the text they had before ISSUE 46, the counters
    beside the pool too, and hold no ``moe_combine``."""
    import json
    import os
    if cell == "olmoe":
        slots, max_len = SLOTS, MAX_LEN
        model = TransformerLM(VOCAB, dim=2048, depth=1, num_heads=16,
                              max_seq_len=MAX_LEN, norm="rmsnorm", rope=True,
                              norm_eps=1e-5, attn_bias=False, qk_norm=True,
                              num_experts=64, moe_top_k=8, moe_hidden=1024,
                              moe_gated=True, moe_normalize_gates=False,
                              moe_dispatch="dropless")
    else:
        from tpu_dist.models import Xing4LM
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "chipbench", "configs",
                               "xing4-29b-a4b-serve.json")) as f:
            cfg = json.load(f)
        slots, max_len = cfg["serve"]["slots"], cfg["serve"]["max_len"]
        kw = {k: cfg[key] for k, key in cfg["model"]["kwargs_from"].items()}
        model = Xing4LM(**dict(kw, depth=2, vocab_size=VOCAB))
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(slots, max_len, jnp.bfloat16)),
        one_chip)
    counters = _shapes(jax.eval_shape(model.init_moe_counters), one_chip)
    params = _param_shapes(model, one_chip)

    def text():
        with nn.attention_impl("flash"):
            return _lower(model, program, params, pool, counters, one_chip,
                          slots=slots, bucket=max_len).as_text()

    now = text()
    _without_the_combine_by_rows(monkeypatch)
    assert "moe_combine" not in now and "combined_rows" in str(counters)
    assert now == text()


# -- a slot entry that is a convolution tail alone (ISSUE 47) ------------------

@pytest.mark.parametrize("program", ["decode_step", "prefill_into_slot"])
def test_lfm2_moe_pool_programs_compile_at_the_cells_shapes(
        one_chip, no_compile_cache, mosaic_gmm, mosaic_decode_attention,
        program, capsys):
    """LFM2-MoE's layers at the published widths, three layers (a gated
    short convolution over the dense MLP of 11,776, a grouped-query
    attention of 32 heads over 8 K/V heads of 64 and a convolution layer
    each over 64 held experts of 1,536; vocabulary cut), at the cell's 320
    slots x 1,024 and its 256 bucket: the chip's compiler takes both pool
    programs; the decode step holds ONE grouped ``decode_attention`` call
    (``G`` = 4, ``D`` = 64) for the attention layer and the grouped matmuls
    named by a step's 1,280 picks, which are no prefill bucket's
    (``chipbench/gmm_ep_need.py`` tells the two apart by them); neither
    program copies the K/V pool, and a convolution layer's tail (2.6 MB for
    320 slots) is made by a fusion, never by a copy."""
    from tpu_dist.models import Lfm2MoeLM
    slots = 320
    model = Lfm2MoeLM(
        VOCAB, dim=2048, depth=3, num_heads=32, num_kv_heads=8,
        layer_types="conv,full_attention,conv", dense_hidden=11776,
        num_dense_layers=1, max_seq_len=MAX_LEN)
    pool = _shapes(jax.eval_shape(
        lambda: model.init_slot_cache(slots, MAX_LEN, jnp.bfloat16)),
        one_chip)
    assert {n: a.shape for n, a in pool["block0.attn"].items()} == {
        "conv": (slots, 4096)}
    assert pool["block1.attn"]["k"].shape == (slots, 8, 64, MAX_LEN)
    counters = _shapes(jax.eval_shape(model.init_moe_counters), one_chip)
    with nn.attention_impl("flash"):        # as a TPU backend would choose
        assert model.slot_decode_kernel(pool) is True
        assert model.slot_state_kernel(pool) is False
        assert model.prefill_scan_kernel(pool, 256) is False
        compiled = _lower(model, program, _param_shapes(model, one_chip),
                          pool, counters, one_chip, slots=slots,
                          bucket=256).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n[lfm2-moe, 3 of 10 layers, {slots} slots] {program}: "
              f"arguments {memory.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"temporaries {memory.temp_size_in_bytes / 2**20:.1f} MiB")
    calls = re.findall(r"%(decode_attention|gmm_r\d+)[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    # a step's 1,280 picks; a prefill program's four prompts of 256 (ISSUE
    # 48): 4,096
    picks = (slots if program == "decode_step"
             else prefill_width(256, MAX_LEN) * 256) * 4
    assert calls.count(f"gmm_r{picks}") == 6, calls          # two layers
    assert calls.count("decode_attention") == (program == "decode_step")
    assert len(calls) == 6 + (program == "decode_step")
    assert not _pool_sized_results(text, ("copy",),
                                   elements=slots * MAX_LEN * 8 * 64)
    tail = re.escape(f"bf16[{slots},4096]")
    assert not [line for line in text.splitlines()
                if re.search(r"= [^=]*%s[^=]* copy\(" % tail, line)]
    assert memory.temp_size_in_bytes < 128 << 20


# -- one backward kernel for flash attention (ISSUE 50) ------------------------

@pytest.mark.parametrize("bh, t, d, dtype, names", [
    # the training cells' call, chip_smoke.py's trainer and float32 operands
    # (2 x 2 tiles of 512): a head's dQ stays in VMEM across the sweep
    (128, 1024, 64, jnp.bfloat16, ["flash_bwd_dq_dkv"]),
    (128, 2048, 64, jnp.bfloat16, ["flash_bwd_dq_dkv"]),
    (128, 1024, 64, jnp.float32, ["flash_bwd_dq_dkv"]),
    # a head's dQ past the estimate: the pair, each making the scores
    (16, 16384, 128, jnp.bfloat16, ["flash_bwd_dkv", "flash_bwd_dq"]),
], ids=["cell", "chip_smoke", "float32", "pair"])
def test_flash_backward_compiles_as_backward_plan_says(
        one_chip, no_compile_cache, mosaic_flash, bh, t, d, dtype, names):
    """The causal backward pass compiled for the described chip (interpret
    mode has missed Mosaic's refusals before: a slice off the tiling, more
    VMEM than a kernel may take): ONE ``flash_bwd_dq_dkv`` call where
    ``backward_plan`` says one kernel, ``flash_bwd_dq`` and ``flash_bwd_dkv``
    where it says two."""
    fa = mosaic_flash
    assert fa.backward_plan(t, t, d, True, dtype=dtype)["kernels"] == len(names)
    x = jax.ShapeDtypeStruct((bh, t, d), dtype, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((bh, t, 1), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda *a: fa._bwd_call(
        *a, True, d ** -0.5, 1024, 1024)).lower(
            x, x, x, x, stat, x).compile().as_text()
    calls = re.findall(r"%(flash_bwd\w+?)[.\d]* = [^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(calls) == names, calls

"""What ISSUE 30 adds to the benchmark, on the CPU: the new configuration and
mix against the contract, the counting functions of ``kernel.gmm_ep_roofline``
on shapes worked out by hand, the three new readers on counters and a reduced
trace made by hand, nothing (not an error) from a program without the counters
(the parent commit), and the cell end to end through run.py and
drivers/serve.py unchanged on a fixture benchmark of its own
(tests/fixture/BENCHMARK.qwen3next.json: a model that holds 4 of its 16
experts, prompts in one 128 bucket)."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

from chipbench import gmm_ep_need, gmm_need, spec

FIXTURE = "chipbench/tests/fixture/BENCHMARK.qwen3next.json"
BENCH = spec.load_benchmark("BENCHMARK.json")
CELL = "serve-qwen3next-longdocs"
REAL = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                   "qwen3-next-80b-a3b-serve.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PUBLISHED = {  # the catalog's config of Qwen3-Next-80B-A3B-Instruct
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 512, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "vocab_size": 151936}


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


# -- the configuration and the mix -------------------------------------------

def test_every_published_width_is_in_the_file_and_only_three_keys_are_cut():
    assert REAL["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in REAL["reduced"]:
            assert REAL["reduced_from"][key] == value and REAL["reduced_how"][
                key]
        else:
            assert REAL[key] == value, key
    # three whole periods, an eighth of the experts and of the vocabulary
    assert REAL["num_hidden_layers"] == 12 == 3 * REAL[
        "full_attention_interval"]
    assert (REAL["num_experts"], REAL["router_num_experts"],
            REAL["expert_offset"]) == (64, 512, 0)
    assert REAL["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    kw = spec.model_kwargs(REAL)
    assert (kw["num_experts"], kw["experts_held"], kw["moe_top_k"],
            kw["moe_hidden"], kw["shared_hidden"]) == (512, 64, 10, 512, 512)
    assert (kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) == (16, 2,
                                                                     256)
    entry = spec.named(BENCH["configs"], REAL["name"], "configuration")
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"]
    sv = REAL["serve"]
    # the issue's traffic: not below 96 slots, two clients a slot
    assert sv["slots"] == 96 and sv["max_len"] == 4096
    assert sv["logit_tol"] > 0 and sv["logit_tol_reason"]
    assert "GiB" in sv["slots_fit"]


def test_the_model_is_built_from_the_file_alone():
    """The factory takes the file's scalars and derives the layer pattern;
    shapes only, nothing of the 2.9B parameters is allocated."""
    import jax
    model = spec.resolve(REAL["model"]["factory"])(**spec.model_kwargs(REAL))
    assert model.layer_kinds == (["linear_attention"] * 3
                                 + ["full_attention"]) * 3
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    assert params["block0.mlp"]["router"].shape == (2048, 512)
    assert params["block0.mlp"]["w1"].shape == (64, 2048, 512)
    assert params["block0.attn"]["qkvz_weight"].shape == (2048, 12288)
    assert params["block3.attn"]["qkv_weight"].shape == (2048, 9216)
    assert params["head"]["weight"].shape == (2048, 18992)
    pool = jax.eval_shape(lambda: model.init_slot_cache(2, 4096))
    assert pool["block3.attn"]["k"].shape == (2, 2, 256, 4096)
    assert pool["block0.attn"]["state"].shape == (2, 32, 128, 128)
    assert pool["block0.attn"]["conv"].shape == (2, 3 * 8192)
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == 2_929_374_400


def test_the_mix_and_the_entries_are_as_the_issue_lists_them():
    cell = spec.named(BENCH["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b-serve", "longdocs-closed", 1)
    assert BENCH["workloads"][-1] is cell
    mix = spec.load_json(spec.find(BENCH, "traffic", "longdocs-closed.json"))
    assert mix["loop"] == "closed" and mix["clients_per_slot"] == 2
    (cls,) = mix["classes"]
    assert cls["prompt_len"] == {"dist": "uniform", "min": 2100, "max": 3900}
    assert cls["output_len"] == {"dist": "uniform", "min": 64, "max": 192}
    assert cls["prompt_len"]["max"] + cls["output_len"]["max"] <= REAL[
        "serve"]["max_len"]
    metrics = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("serve_tokens_per_s", "serve.occupancy",
                 "serve.prefill_share", "serve.decode_share",
                 "serve.loop_host_share", "serve.loop_ahead_share",
                 "kernel.gmm_share", "serve.moe_load_max_over_mean",
                 "kernel.gmm_ep_roofline", "serve.moe_rows_computed_over_held",
                 "serve.state_bytes_share"):
        assert metrics[name]["workloads"][-1] == CELL, name
        assert metrics[name].get("moves", name) == "serve_tokens_per_s"
    # its reader takes the dense width and every pick: over 105% here
    assert CELL not in metrics["kernel.gmm_roofline"]["workloads"]
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == [
        "kernel.gmm_ep_roofline", "serve.moe_rows_computed_over_held",
        "serve.state_bytes_share"]


def test_the_float8_control_runs_the_cell_itself_under_another_reference():
    """tests/fixture/fp8_control/BENCHMARK.json: the cell's own entries,
    configuration file and mix; only the file its ``reference`` names is
    found elsewhere first, and that one rounds every matrix to float8 e4m3
    before the plain reference's forward (PERF.md, PR 30: on the chip the
    run ends ``"correct": false``)."""
    import jax
    import jax.numpy as jnp
    control = spec.load_benchmark(
        "chipbench/tests/fixture/fp8_control/BENCHMARK.json")
    assert control["workloads"] == [spec.named(BENCH["workloads"], CELL,
                                               "cell")]
    assert control["configs"] == [spec.named(BENCH["configs"], REAL["name"],
                                             "configuration")]
    assert control["run_seconds"] == BENCH["run_seconds"]
    assert spec.find(control, "traffic", "longdocs-closed.json") == spec.find(
        BENCH, "traffic", "longdocs-closed.json")
    plain = spec.load_module(spec.find(BENCH, "reference", REAL["reference"]))
    low = spec.load_module(spec.find(control, "reference", REAL["reference"]))
    # the plain reference's own forward, loaded from its file
    assert low.forward.__code__.co_filename == plain.forward.__code__.co_filename
    tiny = spec.load_json(os.path.join(
        spec.ROOT, "chipbench/tests/fixture/configs/tiny-qwen3next-serve.json"))
    model = spec.resolve(tiny["model"]["factory"])(**spec.model_kwargs(tiny))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          model.init(jax.random.key(0)))
    a, b = plain.stack_params(tiny, params), low.stack_params(tiny, params)
    e4m3 = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    assert jnp.array_equal(b["head"], e4m3(a["head"]))
    assert not jnp.array_equal(b["head"], a["head"])
    mix, ctl = a["blocks"][0]["mixer"], b["blocks"][0]["mixer"]
    assert jnp.array_equal(ctl["A_log"], mix["A_log"])       # a vector
    assert jnp.array_equal(ctl["qkvz_weight"], e4m3(mix["qkvz_weight"]))
    seq = jnp.arange(24)[None] % tiny["vocab_size"]
    assert float(jnp.abs(low.forward(tiny, b, seq)
                         - plain.forward(tiny, a, seq)).max()) > 1e-3


# -- the counting functions ---------------------------------------------------

def _moe(pre, dec, keys=("rows", "held_rows", "pad_rows", "computed_rows",
                         "calls", "experts_hit")):
    by = {"prefill": dict(zip(keys, pre)), "decode": dict(zip(keys, dec))}
    total = lambda k: by["prefill"].get(k, 0) + by["decode"].get(k, 0)
    return {"rows_per_expert": [4, 2, 2, 8], "by_phase": by,
            **{k: total(k) for k in keys}}


def test_held_rows_need_on_hand_computed_shapes():
    """A 3,000-token prompt in the 4,096 bucket: 30,000 picks of the
    request, 3,750 of them on the 64 experts held, all 64 reached; 12 calls
    (one a layer)."""
    moe = _moe((30_000 * 12, 3_750 * 12, 10_960 * 12, 8_192 * 12, 12,
                64 * 12),
               (1_280 * 12, 160 * 12, 0, 944 * 12, 12, 59 * 12))
    means = gmm_ep_need.phase_means(moe)
    assert means == {"prefill": (3_750.0, 64.0), "decode": (160.0, 59.0)}
    pre = gmm_need.grouped_matmul(3_750, 64, 2048, 512)
    assert pre["flops"] == 2 * 3_750 * 2048 * 512 == 7_864_320_000
    assert pre["bytes"] == (64 * 2048 * 512 + 3_750 * 2_560) * 2 \
        == 153_417_728
    # memory bound on a v5e: 0.187 ms to read, 0.040 ms to compute
    t, bound = gmm_need.flops.roofline(pre["flops"], pre["bytes"], PEAK)
    assert bound == "memory" and t == pytest.approx(153_417_728 / 819e9)
    ms = 1_000_000      # rows are in ns
    reduced = {"rows0": [
        ("gmm_r40960.3 bf16[10240,512]", 0, 1 * ms),
        ("gmm_r40960.4 bf16[10240,2048]", 1 * ms, 2 * ms),
        ("gmm_r1280.1 bf16[2048,512]", 2 * ms, 3 * ms),
        ("fusion.7 bf16[10240,2048] gmm_r40960.3", 3 * ms, 9 * ms)]}
    dec = gmm_need.grouped_matmul(160, 59, 2048, 512)
    least = (2 * pre["bytes"] + dec["bytes"]) / 819e9
    got = gmm_ep_need.roofline_share(reduced, moe, 1_280, 2048, 512, PEAK)
    assert got == pytest.approx(100 * least / 3e-3)
    assert 0 < got < 100
    # charged with every pick and the dense width, as kernel.gmm_roofline
    # would charge it, the same calls read far over 100%
    old = gmm_need.roofline_share(reduced, moe, 1_280, 2048, 5120, PEAK)
    assert old > 105
    assert gmm_ep_need.roofline_share({"rows0": [("fusion.1", 0, 5)]}, moe,
                                      1_280, 2048, 512, PEAK) is None


def _run(trace, engine, config=REAL):
    return types.SimpleNamespace(
        trace=trace, peak=PEAK, counters={"engine": engine},
        window=(0.0, 30.0), ctx=types.SimpleNamespace(config=config))


def test_the_three_readers():
    ms = 1_000_000
    # a decode step routes slots x 10 picks: the reader tells its calls by
    # that number, read from the configuration
    decode = REAL["serve"]["slots"] * REAL["num_experts_per_tok"]
    trace = {"busy0_s": 8e-3, "rows0": [
        ("gmm_r40960.3 bf16[10240,512]", 0, 2 * ms),
        (f"gmm_r{decode}.9 bf16[2048,2048]", 2 * ms, 4 * ms)]}
    moe = _moe((30_000, 3_750, 10_960, 8_192, 1, 64),
               (decode, 160, 0, 944, 1, 59))
    state = {"state_bytes": 3_000, "kv_bytes": 1_000}
    run = _run(trace, {"moe": moe, "state": state})
    need = gmm_need.grouped_matmul(3_750, 64, 2048, 512)["bytes"] + \
        gmm_need.grouped_matmul(160, 59, 2048, 512)["bytes"]
    assert _reader("kernel.gmm_ep_roofline").read(run) == pytest.approx(
        100 * need / 819e9 / 4e-3)
    assert _reader("serve.moe_rows_computed_over_held").read(run) == \
        pytest.approx((8_192 + 944) / (3_750 + 160))
    assert _reader("serve.state_bytes_share").read(run) == 75.0


@pytest.mark.parametrize("name", ["kernel.gmm_ep_roofline",
                                  "serve.moe_rows_computed_over_held",
                                  "serve.state_bytes_share"])
def test_a_program_without_the_counters_or_a_trace_reads_nothing(name):
    """The parent commit has neither ``stats()["state"]`` nor ``held_rows``
    among ``stats()["moe"]``; a dense model has no ``"moe"`` at all; an
    untraced or CPU run has no trace: None, never a raise."""
    read = _reader(name).read
    trace = {"busy0_s": 1.0, "rows0": [("gmm_r1280.1", 0, 5)]}
    parent = _moe((8_192, 0, 1, 64), (256, 0, 1, 64),
                  keys=("rows", "pad_rows", "calls", "experts_hit"))
    olmoe = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                        "olmoe-1b-7b-serve.json"))
    for engine in ({}, {"moe": parent}):
        assert read(_run({}, engine)) is None
        assert read(_run(trace, engine)) is None
        assert read(_run(trace, engine, olmoe)) is None
    assert read(types.SimpleNamespace(trace=trace, peak=PEAK, counters={},
                                      ctx=types.SimpleNamespace(
                                          config=REAL))) is None


# -- the cell through run.py --------------------------------------------------

def test_the_cell_runs_through_run_py_unchanged():
    """A hybrid model that holds a share of its experts through build /
    warm-up / window / verifier of chipbench/drivers/serve.py as it is, seed
    above 2**31, traced: the counter metrics are read, the trace metrics find
    no device and say nothing, and the served tokens are the reference's,
    given the same share."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", FIXTURE,
         "--rehearse", "--workload", "tiny-qwen3next-longdocs", "--seed",
         "3000000007", "--seconds", "3", "--trace", "1"], cwd=spec.ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    said = dict(re.findall(r"\[chipbench\]   ([\w.]+): (\S+) ", p.stdout))
    assert said["compile.in_window"] == "0"
    assert 1.0 <= float(said["serve.moe_load_max_over_mean"]) < 8.0
    assert 1.0 <= float(said["serve.moe_rows_computed_over_held"]) < 16.0
    assert 0.0 < float(said["serve.state_bytes_share"]) < 100.0
    assert said["kernel.gmm_share"] == said["kernel.gmm_ep_roofline"] == "None"

"""What ISSUE 32 adds to the benchmark, on the CPU: the new configuration and
mix against the contract and the catalog's published values, the counting
functions of ``serve.decode_roofline``, ``serve.latent_read_share`` and
``kernel.mla_decode_roofline`` on shapes worked out by hand, the three new
readers on counters and a reduced trace made by hand, nothing (not an error)
from a program without the counter (the parent commit), the float8 control,
and the cell end to end through run.py and drivers/serve.py unchanged on a
fixture benchmark of its own (tests/fixture/BENCHMARK.kimik2.json: a
latent-attention model that holds 4 of its 16 experts, prompts in one 64
bucket)."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

from chipbench import decode_need, mla_need, spec

FIXTURE = "chipbench/tests/fixture/BENCHMARK.kimik2.json"
CONTROL = "chipbench/tests/fixture/fp8_control_kimik2/BENCHMARK.json"
BENCH = spec.load_benchmark("BENCHMARK.json")
CELL = "serve-kimik2-agent"
REAL = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                   "kimi-k2-serve.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PUBLISHED = {  # the catalog's config of Kimi-K2-Instruct
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "kimi_k2",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 384, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


# -- the configuration and the mix -------------------------------------------

def test_every_published_key_is_in_the_file_and_only_three_are_cut():
    assert REAL["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in REAL["reduced"]:
            assert REAL["reduced_from"][key] == value and REAL["reduced_how"][
                key]
        else:
            assert REAL[key] == value, key
    # the leading dense layer + 4 of the 60 that follow, 12 of 384 experts
    # (1/32), an eighth of the vocabulary: the guide's floors
    assert REAL["num_hidden_layers"] == REAL["first_k_dense_replace"] + 4
    assert (REAL["n_routed_experts"], REAL["router_num_experts"],
            REAL["expert_offset"]) == (12, 384, 0)
    assert REAL["n_routed_experts"] >= 8
    assert REAL["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the factory takes scalars: rope_scaling's entries repeated flat, each
    # under its published name, and held to the group the reference reads
    for key, value in PUBLISHED["rope_scaling"].items():
        if key != "type":
            assert REAL["rope_scaling_" + key] == value, key
    kw = spec.model_kwargs(REAL)
    assert all(isinstance(v, (int, float, bool, str)) for v in kw.values())
    assert (kw["num_experts"], kw["experts_held"], kw["moe_top_k"],
            kw["moe_hidden"], kw["dense_hidden"]) == (384, 12, 8, 2048, 18432)
    assert (kw["num_heads"], kw["q_lora_rank"], kw["kv_lora_rank"],
            kw["qk_nope_head_dim"], kw["qk_rope_head_dim"],
            kw["v_head_dim"]) == (64, 1536, 512, 128, 64, 128)
    entry = spec.named(BENCH["configs"], REAL["name"], "configuration")
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"]
    assert BENCH["configs"][-1] is entry and len(entry["why"]) <= 200
    sv = REAL["serve"]
    # the issue's traffic: 128 slots (not below 96), two clients a slot
    assert 96 <= sv["slots"] <= 192 and sv["max_len"] == 4096
    assert sv["logit_tol"] > 0 and sv["logit_tol_reason"]
    assert "GiB" in sv["slots_fit"]
    assert "256 chips" in REAL["deployment"] and "32 chips" in REAL[
        "deployment"]
    for key in ("e_score_correction_bias", "routed_experts", "expert_load"):
        assert REAL["assumed"][key]


def test_the_model_is_built_from_the_file_alone():
    """The factory takes the file's scalars and derives the layer kinds;
    shapes only, nothing of the 3.5B parameters is allocated."""
    import jax
    model = spec.resolve(REAL["model"]["factory"])(**spec.model_kwargs(REAL))
    assert model.layer_kinds == ["dense"] + ["moe"] * 4
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    assert params["block0.mlp.gate"]["weight"].shape == (7168, 18432)
    assert params["block1.mlp"]["router"].shape == (7168, 384)
    assert params["block1.mlp"]["router_bias"].shape == (384,)
    assert params["block1.mlp"]["w1"].shape == (12, 7168, 2048)
    assert "shared_gate" not in params["block1.mlp"]
    attn = params["block4.attn"]
    assert attn["q_a_weight"].shape == (7168, 1536)
    assert attn["q_b_weight"].shape == (1536, 64 * 192)
    assert attn["kv_a_weight"].shape == (7168, 576)
    assert attn["kv_b_weight"].shape == (512, 64 * 256)
    assert attn["out_weight"].shape == (8192, 7168)
    assert params["head"]["weight"].shape == (7168, 20480)
    # the cache holds 576 numbers a position a layer, no heads
    pool = jax.eval_shape(lambda: model.init_slot_cache(2, 4096))
    assert all(set(e) == {"latent"} and e["latent"].shape == (2, 576, 4096)
               for e in pool.values()) and len(pool) == 5
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == 3_496_763_904
    assert model.block0.attn.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 3.4657359 + 1) ** 2, rel=1e-6)


def test_the_mix_and_the_entries_are_as_the_issue_lists_them():
    cell = spec.named(BENCH["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2-serve", "agent-closed", 1)
    assert BENCH["workloads"][-1] is cell and len(BENCH["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    mix = spec.load_json(spec.find(BENCH, "traffic", "agent-closed.json"))
    assert mix["loop"] == "closed" and mix["clients_per_slot"] == 2
    assert mix["users"]
    (cls,) = mix["classes"]
    assert cls["prompt_len"] == {"dist": "uniform", "min": 1100, "max": 2000}
    assert cls["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    # one 2,048 bucket, and the longest request fits a slot
    assert 1024 < cls["prompt_len"]["min"] and cls["prompt_len"]["max"] <= 2048
    assert cls["prompt_len"]["max"] + cls["output_len"]["max"] <= REAL[
        "serve"]["max_len"]
    assert mix["trace_from_s"] + mix["trace_seconds"] < BENCH["run_seconds"]
    metrics = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("serve_tokens_per_s", "serve.occupancy",
                 "serve.prefill_share", "serve.decode_share",
                 "serve.loop_host_share", "serve.loop_ahead_share",
                 "kernel.gmm_share", "kernel.gmm_ep_roofline",
                 "serve.moe_load_max_over_mean",
                 "serve.moe_rows_computed_over_held"):
        assert metrics[name]["workloads"][-1] == CELL, name
        assert metrics[name].get("moves", name) == "serve_tokens_per_s"
    # its reader takes the dense width and every pick: over 105% here
    assert CELL not in metrics["kernel.gmm_roofline"]["workloads"]
    new = BENCH["per_layer"][-3:]
    assert [m["name"] for m in new] == ["serve.latent_read_share",
                                        "serve.decode_roofline",
                                        "kernel.mla_decode_roofline"]
    assert all(m["workloads"] == [CELL] and m["unit"] == "%"
               and m["moves"] == "serve_tokens_per_s" for m in new)
    assert [m["layer"] for m in new] == ["serve_model_step",
                                         "serve_model_step", "kernels"]
    # the reader of kernel.gmm_ep_roofline finds its widths under the keys
    # the published file has
    assert {"hidden_size", "moe_intermediate_size",
            "num_experts_per_tok"} <= set(REAL)


def test_the_float8_control_runs_the_cell_itself_under_another_reference():
    """tests/fixture/fp8_control_kimik2/BENCHMARK.json: the cell's own
    entries, configuration file and mix; only the file its ``reference``
    names is found elsewhere first, and that one rounds every matrix to
    float8 e4m3 before the plain reference's forward (PERF.md, PR 32: on
    the chip the run ends ``"correct": false``)."""
    import jax
    import jax.numpy as jnp
    control = spec.load_benchmark(CONTROL)
    assert control["workloads"] == [spec.named(BENCH["workloads"], CELL,
                                               "cell")]
    assert control["configs"] == [spec.named(BENCH["configs"], REAL["name"],
                                             "configuration")]
    assert control["run_seconds"] == BENCH["run_seconds"]
    assert spec.find(control, "traffic", "agent-closed.json") == spec.find(
        BENCH, "traffic", "agent-closed.json")
    plain = spec.load_module(spec.find(BENCH, "reference", REAL["reference"]))
    low = spec.load_module(spec.find(control, "reference", REAL["reference"]))
    assert low.forward.__code__.co_filename == plain.forward.__code__.co_filename
    tiny = spec.load_json(os.path.join(
        spec.ROOT, "chipbench/tests/fixture/configs/tiny-kimik2-serve.json"))
    model = spec.resolve(tiny["model"]["factory"])(**spec.model_kwargs(tiny))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          model.init(jax.random.key(0)))
    a, b = plain.stack_params(tiny, params), low.stack_params(tiny, params)
    e4m3 = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    assert jnp.array_equal(b["head"], e4m3(a["head"]))
    assert not jnp.array_equal(b["head"], a["head"])
    own, ctl = a["blocks"][1], b["blocks"][1]
    assert jnp.array_equal(ctl["mlp"]["router_bias"],
                           own["mlp"]["router_bias"])          # a vector
    assert jnp.array_equal(ctl["attn"]["kv_b_weight"],
                           e4m3(own["attn"]["kv_b_weight"]))
    seq = jnp.arange(24)[None] % tiny["vocab_size"]
    assert float(jnp.abs(low.forward(tiny, b, seq)
                         - plain.forward(tiny, a, seq)).max()) > 1e-3


def test_the_reference_imports_nothing_of_the_program():
    for path in (spec.find(BENCH, "reference", REAL["reference"]),):
        text = open(path).read()
        assert "tpu_dist" not in re.sub(r'""".*?"""', "", text, flags=re.S)
        assert 'default_matmul_precision("highest")' in text


# -- the counting functions ---------------------------------------------------

# one decode step of the cell, by hand: 128 busy slots that hold 243,200
# columns (1,899 each + the one written); 3.2 GB of weights; 5 layers
NEED = {"steps": 1, "rows": 128, "positions": 243_200,
        "weight_bytes": 6_300_000_000,
        "cache_bytes": 243_200 * 5 * 576 * 2,
        "flops": 2 * 3_000_000_000 * 128 + 5 * 2 * 64 * 1088 * 243_200}


def test_decode_need_on_hand_computed_shapes():
    assert NEED["cache_bytes"] == 1_400_832_000
    t, bound = decode_need.least_seconds(NEED, PEAK)
    # 7.70 GB at 819 GB/s = 9.4 ms against 0.94 TFLOP at 197 = 4.8 ms
    assert bound == "memory"
    assert t == pytest.approx((6.3e9 + 1_400_832_000) / 819e9)
    assert NEED["flops"] / 197e12 == pytest.approx(4.76e-3, rel=1e-2)
    assert decode_need.roofline_share(NEED, 20e-3, PEAK) == pytest.approx(
        100 * t / 20e-3)
    assert decode_need.latent_read_share(NEED) == pytest.approx(
        100 * 1_400_832_000 / 7_700_832_000)
    # compute bound where a step carries rows enough
    wide = dict(NEED, flops=NEED["flops"] * 4)
    assert decode_need.least_seconds(wide, PEAK)[1] == "compute"
    assert decode_need.roofline_share(dict(NEED, steps=0), 1.0, PEAK) is None
    assert decode_need.roofline_share(NEED, 0.0, PEAK) is None
    assert decode_need.latent_read_share({}) is None


def test_the_kernels_need_on_hand_computed_shapes():
    """One call: 243,200 columns of 576 bfloat16 read once, 128 x 64 query
    rows of 576 read and 512 written; 2 x 64 x (576 + 512) operations a
    column: 0.345 ms to read against 0.172 ms to compute."""
    one = mla_need.call(128, 243_200, 64, 576, 512)
    assert one["bytes"] == (243_200 * 576 + 128 * 64 * 1088) * 2 \
        == 297_992_192
    assert one["flops"] == 2 * 64 * 1088 * 243_200 == 33_869_004_800
    t, bound = mla_need.flops.roofline(one["flops"], one["bytes"], PEAK)
    assert bound == "memory" and t == pytest.approx(297_992_192 / 819e9)
    ms = 1_000_000      # rows are in ns
    reduced = {"rows0": [
        ("latent_decode_attention.3 (bf16[128,64,512], bf16[128,576,4096])",
         0, 1 * ms),
        ("latent_decode_attention.4", 1 * ms, 2 * ms),
        ("fusion.7 bf16[128,64,512] latent_decode_attention.3", 2 * ms,
         9 * ms),
        ("decode_attention.1", 9 * ms, 10 * ms)]}
    got = mla_need.roofline_share(reduced, NEED, 64, 576, 512, PEAK)
    assert got == pytest.approx(100 * 2 * t / 2e-3)
    assert 0 < got < 100
    assert mla_need.roofline_share({"rows0": [("fusion.1", 0, 5)]}, NEED,
                                   64, 576, 512, PEAK) is None
    assert mla_need.roofline_share(reduced, dict(NEED, steps=0), 64, 576, 512,
                                   PEAK) is None


def _run(trace, engine, config=REAL, peak=PEAK):
    return types.SimpleNamespace(
        trace=trace, peak=peak, counters={"engine": engine},
        window=(0.0, 30.0), ctx=types.SimpleNamespace(config=config))


def test_the_three_readers():
    ms = 1_000_000
    trace = {"busy0_s": 8e-3, "rows0": [
        ("latent_decode_attention.3", 0, 1 * ms),
        ("latent_decode_attention.9", 2 * ms, 3 * ms)]}
    hist = {"count": 1, "mean": 20e-3}
    run = _run(trace, {"decode_need": NEED, "decode_step": hist})
    assert _reader("serve.latent_read_share").read(run) == pytest.approx(
        18.19, abs=0.01)
    assert _reader("serve.decode_roofline").read(run) == pytest.approx(
        100 * 7_700_832_000 / 819e9 / 20e-3)
    assert _reader("kernel.mla_decode_roofline").read(run) == pytest.approx(
        100 * 2 * 297_992_192 / 819e9 / 2e-3)


@pytest.mark.parametrize("name", ["serve.latent_read_share",
                                  "serve.decode_roofline",
                                  "kernel.mla_decode_roofline"])
def test_a_program_without_the_counter_or_a_trace_reads_nothing(name):
    """The parent commit has no ``stats()["decode_need"]`` and no such
    kernel; a window may hold no decode step; an untraced or CPU run has no
    trace and a rehearsal no peaks: None, never a raise."""
    read = _reader(name).read
    trace = {"busy0_s": 1.0, "rows0": [("latent_decode_attention.1", 0, 5)]}
    hist = {"count": 3, "mean": 20e-3}
    olmoe = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                        "olmoe-1b-7b-serve.json"))
    for engine in ({}, {"decode_step": hist},
                   {"decode_step": hist, "decode_need": {}},
                   {"decode_step": {"count": 0, "mean": 0.0},
                    "decode_need": dict(NEED, steps=0, weight_bytes=0,
                                        cache_bytes=0, flops=0)}):
        assert read(_run({}, engine)) is None
        assert read(_run(trace, engine)) is None
    assert read(types.SimpleNamespace(trace=trace, peak=PEAK, counters={},
                                      window=(0.0, 30.0),
                                      ctx=types.SimpleNamespace(
                                          config=REAL))) is None
    full = {"decode_step": hist, "decode_need": NEED}
    if name != "serve.latent_read_share":
        assert read(_run(trace, full, peak=None)) is None   # a rehearsal
    if name == "kernel.mla_decode_roofline":
        assert read(_run(trace, full, olmoe)) is None    # no latent widths
        assert read(_run({"rows0": [("fusion.1", 0, 5)]}, full)) is None


# -- the cell through run.py --------------------------------------------------

def test_the_cell_runs_through_run_py_unchanged():
    """A latent-attention model that holds a share of its experts through
    build / warm-up / window / verifier of chipbench/drivers/serve.py as it
    is, seed above 2**31, traced: the counter metrics are read, the trace
    metrics and those that need a chip's peaks say nothing, and the served
    tokens are the reference's, given the same share."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", FIXTURE,
         "--rehearse", "--workload", "tiny-kimik2-agent", "--seed",
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
    assert 0.0 < float(said["serve.latent_read_share"]) < 100.0
    assert (said["kernel.gmm_share"] == said["kernel.gmm_ep_roofline"]
            == said["serve.decode_roofline"]
            == said["kernel.mla_decode_roofline"] == "None")

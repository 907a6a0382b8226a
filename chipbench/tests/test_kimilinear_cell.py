"""What ISSUE 40 adds to the benchmark, on the CPU: the new configuration and
mix against the contract and the catalog's published values, the counting
function of ``serve.state_need_share`` on shapes worked out by hand and
against the program's own counter, the new reader on counters made by hand,
nothing (not an error) from a program without the counter, the float8 and
the fault controls, and the cell end to end through run.py and
drivers/serve.py unchanged on a fixture benchmark of its own
(tests/fixture/BENCHMARK.kimilinear.json: a hybrid of Kimi Delta Attention
and latent attention without positions that holds 4 of 16 experts, prompts
in one 32 bucket), ``correct`` true, and false under each control.  Every
entry is looked up BY NAME and membership of lists is asserted, never a
position in a list (PERF.md section 7 (3)): a later PR's appends break
nothing here."""

import inspect
import json
import os
import re
import subprocess
import sys
import types

import pytest

from chipbench import spec, state_need

FIXTURE = "chipbench/tests/fixture/BENCHMARK.kimilinear.json"
CONTROLS = {"fp8": "chipbench/tests/fixture/fp8_control_kimilinear",
            "fault": "chipbench/tests/fixture/fault_control_kimilinear"}
BENCH = spec.load_benchmark("BENCHMARK.json")
CELL = "serve-kimilinear-reason"
REAL = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                   "kimi-linear-48b-a3b-serve.json"))
TINY = spec.load_json(os.path.join(
    spec.ROOT, "chipbench/tests/fixture/configs/tiny-kimilinear-serve.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PUBLISHED = {  # the catalog's config of Kimi-Linear-48B-A3B-Instruct
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
LISTED = ("serve.occupancy", "serve.prefill_share", "serve.decode_share",
          "serve.loop_host_share", "serve.loop_ahead_share",
          "serve.loop_unnamed_share", "serve.loop_offcpu_share",
          "serve.gc_share", "kernel.gmm_share", "kernel.gmm_ep_roofline",
          "serve.moe_load_max_over_mean",
          "serve.moe_rows_computed_over_held", "serve.state_bytes_share",
          "serve.latent_read_share", "serve.decode_roofline",
          "kernel.mla_decode_roofline", "serve.state_need_share")
NOT_LISTED = ("kernel.gmm_roofline", "serve.prefill_flash_share",
              "serve.residual_need_share")


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


# -- the configuration and the mix -------------------------------------------

def test_every_published_key_is_in_the_file_and_only_three_are_cut():
    assert sorted(REAL["reduced"]) == ["num_experts", "num_hidden_layers",
                                       "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in REAL["reduced"]:
            assert REAL["reduced_from"][key] == value and REAL["reduced_how"][
                key]
        else:
            assert REAL[key] == value, key
    # the first stage: a leading dense layer + 13 that follow, whole periods
    # first; an eighth of the experts and of the vocabulary
    assert (REAL["num_hidden_layers"], REAL["first_k_dense_replace"]) == (
        14, 1)
    assert (REAL["num_experts"], REAL["router_num_experts"],
            REAL["expert_offset"]) == (32, 256, 0)
    assert REAL["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # no width among the cuts
    assert not any(re.search(r"size|_dim|_rank|per_tok", key)
                   and key != "vocab_size" for key in REAL["reduced"])
    # the harness hands a factory top-level keys: the group's five entries
    # repeated flat, held to the group the reference reads
    lin = PUBLISHED["linear_attn_config"]
    text = lambda layers: ",".join(map(str, layers))
    assert (REAL["kda_layers"], REAL["full_attn_layers"]) == (
        text(lin["kda_layers"]), text(lin["full_attn_layers"]))
    assert (REAL["linear_num_heads"], REAL["linear_head_dim"],
            REAL["linear_short_conv_kernel_size"]) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    # ... and the name an accepted reader knows the picks a token by
    assert REAL["num_experts_per_tok"] == REAL["num_experts_per_token"]
    kw = spec.model_kwargs(REAL)
    assert all(isinstance(v, (int, float, bool, str)) for v in kw.values())
    assert (kw["num_experts"], kw["experts_held"], kw["moe_top_k"],
            kw["moe_hidden"], kw["dense_hidden"]) == (256, 32, 8, 1024, 9216)
    assert (kw["num_heads"], kw["kv_lora_rank"], kw["qk_nope_head_dim"],
            kw["qk_rope_head_dim"], kw["v_head_dim"],
            kw["mla_use_nope"]) == (32, 512, 128, 64, 128, True)
    # null is no scalar: the published q_lora_rank is the factory's default
    factory = spec.resolve(REAL["model"]["factory"])
    assert "q_lora_rank" not in kw and inspect.signature(
        factory).parameters["q_lora_rank"].default is REAL["q_lora_rank"]
    entry = spec.named(BENCH["configs"], REAL["name"], "configuration")
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"]
    assert entry["file"] == "chipbench/configs/kimi-linear-48b-a3b-serve.json"
    assert len(entry["why"]) <= 200
    sv = REAL["serve"]
    assert 96 <= sv["slots"] <= 128 and sv["max_len"] == 1024
    assert sv["logit_tol"] > 0 and sv["logit_tol_reason"]
    assert "GiB" in sv["slots_fit"] and "128" in sv["slots_fit"]
    assert "16 v5e chips" in REAL["deployment"]
    assert "two pipeline stages" in REAL["deployment"]
    for key in ("A_log", "dt_bias", "recurrent_state_dtype",
                "e_score_correction_bias", "routed_experts", "expert_load",
                "max_len"):
        assert REAL["assumed"][key]
    assert REAL["departures"]


def test_the_model_is_built_from_the_file_alone():
    """The factory takes the file's keys and derives the layer kinds from
    the published lists; shapes only, nothing of the 3.72B parameters is
    allocated."""
    import jax
    import jax.numpy as jnp
    from tpu_dist import nn
    model = spec.resolve(REAL["model"]["factory"])(**spec.model_kwargs(REAL))
    assert model.mixer_kinds == (["kda"] * 3 + ["full_attention"]) * 3 + [
        "kda"] * 2
    assert model.layer_kinds == ["dense"] + ["moe"] * 13
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    assert params["block0.mlp.gate"]["weight"].shape == (2304, 9216)
    assert params["block1.mlp"]["router"].shape == (2304, 256)
    assert params["block1.mlp"]["router_bias"].shape == (256,)
    assert params["block1.mlp"]["w1"].shape == (32, 2304, 1024)
    assert params["block1.mlp"]["shared_w1"].shape == (2304, 1024)
    assert "shared_gate" not in params["block1.mlp"]
    kda = params["block13.attn"]
    assert kda["q_weight"].shape == kda["k_weight"].shape == (2304, 4096)
    assert kda["f_a_weight"].shape == (2304, 128)
    assert kda["f_b_weight"].shape == kda["g_b_weight"].shape == (128, 4096)
    assert kda["b_weight"].shape == (2304, 32)
    assert kda["A_log"].shape == (32,) and kda["dt_bias"].shape == (4096,)
    assert kda["v_conv_weight"].shape == (4096, 4)
    assert kda["out_weight"].shape == (4096, 2304)
    attn = params["block3.attn"]
    assert attn["q_weight"].shape == (2304, 32 * 192)
    assert "q_a_weight" not in attn and "q_b_weight" not in attn
    assert attn["kv_a_weight"].shape == (2304, 576)
    assert attn["kv_b_weight"].shape == (512, 32 * 256)
    assert attn["out_weight"].shape == (4096, 2304)
    assert model.block3.attn.softmax_scale == pytest.approx(192 ** -0.5)
    assert params["head"]["weight"].shape == (2304, 20480)
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    # by hand: a KDA mixer, a latent mixer, an expert, the dense MLP,
    # router + bias + shared, the norms
    kda_n = 4 * 2304 * 4096 + 3 * 4096 * 4 + 2 * (2304 * 128 + 128 * 4096) \
        + 2304 * 32 + 32 + 4096 + 128
    mla_n = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    expert = 3 * 2304 * 1024
    assert size(kda) == kda_n == 39_514_272 and size(attn) == mla_n
    assert expert == 7_077_888
    moe_n = 32 * expert + 2304 * 256 + 256 + expert
    total = (11 * kda_n + 3 * mla_n + 3 * 2304 * 9216 + 13 * moe_n
             + 14 * 2 * 2304 + 2304 + 2 * 20480 * 2304)
    assert size(params) == total == 3_724_226_272
    # a slot: 11 layers of float32 state and three bfloat16 tails, whatever
    # the context; 576 bfloat16 a position in each of 3 layers
    pool = jax.eval_shape(
        lambda: model.init_slot_cache(2, 1024, jnp.bfloat16))
    assert len(pool) == 14
    assert set(pool["block0.attn"]) == {"state", "conv_q", "conv_k",
                                        "conv_v"}
    assert pool["block0.attn"]["state"].shape == (2, 32, 128, 128)
    assert pool["block0.attn"]["conv_k"].shape == (2, 3 * 4096)
    assert set(pool["block3.attn"]) == {"latent"}
    assert pool["block3.attn"]["latent"].shape == (2, 576, 1024)
    assert nn.cache.slot_bytes(pool) == (
        11 * (32 * 128 * 128 * 4 + 73_728), 3 * 576 * 2) == (23_879_680,
                                                              3_456)
    assert state_need.slot_state_bytes(11, 32, 128, 128, 3 * 3 * 4096) \
        == 23_879_680
    # a 256 bucket stays under the flash kernel's least sequence
    assert model.prefill_attention_facts(256)["kernel"] is False


def test_the_mix_and_the_entries_are_as_the_issue_lists_them():
    cell = spec.named(BENCH["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b-serve", "reason-closed", 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) \
        == len(BENCH["workloads"])
    mix = spec.load_json(spec.find(BENCH, "traffic", "reason-closed.json"))
    assert mix["kind"] == "requests" and mix["loop"] == "closed"
    assert mix["clients_per_slot"] == 2 and mix["users"]
    (cls,) = mix["classes"]
    assert cls["prompt_len"] == {"dist": "uniform", "min": 130, "max": 250}
    assert cls["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    assert (mix["trace_from_s"], mix["trace_seconds"]) == (18, 3)
    # agent-closed's answers to the number
    other = spec.load_json(spec.find(BENCH, "traffic", "agent-closed.json"))
    assert cls["output_len"] == other["classes"][0]["output_len"]
    # one 256 bucket, and the longest request fits a slot
    assert 128 < cls["prompt_len"]["min"] and cls["prompt_len"]["max"] <= 256
    assert cls["prompt_len"]["max"] + cls["output_len"]["max"] <= REAL[
        "serve"]["max_len"]
    assert mix["trace_from_s"] + mix["trace_seconds"] < BENCH["run_seconds"]
    metrics = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("serve_tokens_per_s",) + LISTED:
        assert CELL in metrics[name]["workloads"], name
        assert metrics[name].get("moves", name) == "serve_tokens_per_s"
    for name in NOT_LISTED:
        assert CELL not in metrics[name]["workloads"], name
    new = metrics["serve.state_need_share"]
    assert {k: v for k, v in new.items() if k != "workloads"} == {
        "name": "serve.state_need_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serve_model_step",
        "moves": "serve_tokens_per_s"}
    assert {CELL, "serve-qwen3next-longdocs"} <= set(new["workloads"])
    # the readers of the shared metrics find their widths under the keys
    # the file has
    assert {"hidden_size", "moe_intermediate_size", "num_experts_per_tok",
            "num_attention_heads", "kv_lora_rank",
            "qk_rope_head_dim"} <= set(REAL)


@pytest.mark.parametrize("which", sorted(CONTROLS))
def test_a_control_runs_the_cell_itself_under_another_reference(which):
    """tests/fixture/{fp8,fault}_control_kimilinear/BENCHMARK.json: the
    cell's own entries, configuration file and mix; only the file its
    ``reference`` names is found elsewhere first."""
    control = spec.load_benchmark(CONTROLS[which] + "/BENCHMARK.json")
    assert control["workloads"] == [spec.named(BENCH["workloads"], CELL,
                                               "cell")]
    assert control["configs"] == [spec.named(BENCH["configs"], REAL["name"],
                                             "configuration")]
    assert control["run_seconds"] == BENCH["run_seconds"]
    assert control["paths"] == [CONTROLS[which], "chipbench"]
    assert spec.find(control, "traffic", "reason-closed.json") == spec.find(
        BENCH, "traffic", "reason-closed.json")
    assert spec.find(control, "reference", REAL["reference"]) != spec.find(
        BENCH, "reference", REAL["reference"])
    assert {m["name"] for m in control["per_layer"]} >= set(LISTED)


def test_the_float8_control_rounds_the_matrices_and_nothing_else():
    """Every matrix a matmul reads rounded to float8 e4m3, inside the plain
    reference's own forward: the weakest float8 computation there is
    (PERF.md, PR 40: on the chip the run ends ``"correct": false``)."""
    import jax
    import jax.numpy as jnp
    control = spec.load_benchmark(CONTROLS["fp8"] + "/BENCHMARK.json")
    plain = spec.load_module(spec.find(BENCH, "reference", REAL["reference"]))
    low = spec.load_module(spec.find(control, "reference", REAL["reference"]))
    assert low.forward.__code__.co_filename == plain.forward.__code__.co_filename
    model = spec.resolve(TINY["model"]["factory"])(**spec.model_kwargs(TINY))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          model.init(jax.random.key(0)))
    a, b = plain.stack_params(TINY, params), low.stack_params(TINY, params)
    e4m3 = lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    assert jnp.array_equal(b["head"], e4m3(a["head"]))
    assert not jnp.array_equal(b["head"], a["head"])
    own, ctl = a["blocks"][1]["mixer"], b["blocks"][1]["mixer"]
    for name in ("q_weight", "f_a_weight", "f_b_weight", "g_b_weight",
                 "b_weight", "out_weight"):
        assert jnp.array_equal(ctl[name], e4m3(own[name])), name
    for name in ("A_log", "dt_bias", "norm_weight", "q_conv_weight",
                 "k_conv_weight", "v_conv_weight"):
        assert jnp.array_equal(ctl[name], own[name]), name
    assert jnp.array_equal(b["blocks"][3]["mixer"]["kv_b_weight"],
                           e4m3(a["blocks"][3]["mixer"]["kv_b_weight"]))
    assert jnp.array_equal(b["blocks"][1]["mlp"]["router_bias"],
                           a["blocks"][1]["mlp"]["router_bias"])
    # the experts' stacks pass as they are (a rounded copy of them does not
    # fit beside the parameters on the chip) and are rounded where read
    assert b["blocks"][1]["mlp"]["w1"] is params["block1.mlp"]["w1"]
    g, u, d = (a["blocks"][1]["mlp"][k][0] for k in ("w1", "w3", "w2"))
    h = jnp.ones((3, g.shape[0]), jnp.float32)
    assert jnp.array_equal(low._plain.gated_mlp(g, u, d, h),
                           plain.gated_mlp(e4m3(g), e4m3(u), e4m3(d), h))
    assert not jnp.array_equal(low._plain.gated_mlp(g, u, d, h),
                               plain.gated_mlp(g, u, d, h))
    assert low._plain.kda.__code__.co_code == plain.kda.__code__.co_code


def test_the_reference_imports_nothing_of_the_program_or_of_its_siblings():
    text = open(spec.find(BENCH, "reference", REAL["reference"])).read()
    code = re.sub(r'""".*?"""', "", text, flags=re.S)
    assert "tpu_dist" not in code and "kimi_k2" not in code
    assert "qwen3_next" not in code
    assert not re.search(r"^\s*(from|import) (?!__future__|jax)", code,
                         flags=re.M)
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(token" in code         # positions, not chunks


# -- the counting function ----------------------------------------------------

# the cell by hand: 11 recurrent layers of 32 heads of 128 x 128 float32 and
# three bfloat16 tails of 3 x 4,096; 120 busy slots a step
def test_state_need_on_hand_computed_shapes():
    slot = state_need.slot_state_bytes(11, 32, 128, 128, 3 * 3 * 4096)
    assert slot == 11 * (2_097_152 + 73_728) == 23_879_680
    a_step = state_need.bytes_moved(120, slot)
    assert a_step == 2 * 120 * 23_879_680 == 5_731_123_200
    # 7.0 ms a step at 819 GB/s (the issue's 6.1 GB and 7.5 ms at 128 slots)
    assert a_step / 819e9 == pytest.approx(7.0e-3, rel=1e-2)
    assert state_need.bytes_moved(128, slot) == pytest.approx(6.11e9,
                                                              rel=1e-2)
    state = {"state_bytes": 800 * a_step, "kv_bytes": 800 * 200_000_000}
    least = state_need.least_seconds(state, PEAK)
    assert least == pytest.approx(800 * 5_731_123_200 / 819e9)
    assert state_need.need_share(state, 28.0, PEAK) == pytest.approx(
        100 * least / 28.0)
    assert 0 < state_need.need_share(state, 28.0, PEAK) < 100
    assert state_need.need_share(state, 0.0, PEAK) is None
    assert state_need.need_share({}, 1.0, PEAK) is None
    assert state_need.need_share({"state_bytes": 0, "kv_bytes": 9}, 1.0,
                                 PEAK) is None


def test_the_programs_counter_is_the_need_functions_arithmetic():
    """``SlotEngine.stats()["state"]`` on the fixture model against
    ``state_need`` from the configuration's shapes."""
    import jax
    from tpu_dist import serve
    model = spec.resolve(TINY["model"]["factory"])(**spec.model_kwargs(TINY))
    eng = serve.SlotEngine(model, model.init(jax.random.key(0)), num_slots=2,
                           max_len=64, min_bucket=32)
    eng.admit(serve.Request(list(range(1, 20)), max_new_tokens=4))
    eng.reset_stats()
    for _ in range(3):
        eng.step()
    lin = TINY["linear_attn_config"]
    slot = state_need.slot_state_bytes(
        3, lin["num_heads"], lin["head_dim"], lin["head_dim"],
        3 * 3 * lin["num_heads"] * lin["head_dim"], tail_itemsize=4)
    got = eng.stats()["state"]
    assert got["state_bytes"] == state_need.bytes_moved(3, slot)
    # one latent layer of 24 float32 a position: 20, 21, 22 resident
    assert got["kv_bytes"] == 24 * 4 * (20 + 21 + 22)


def _run(engine, peak=PEAK):
    return types.SimpleNamespace(
        trace={}, peak=peak, counters={"engine": engine},
        window=(0.0, 30.0), ctx=types.SimpleNamespace(config=REAL))


STATE = {"state_bytes": 800 * 5_731_123_200, "kv_bytes": 800 * 200_000_000}


def test_the_reader():
    run = _run({"state": STATE,
                "prefill": {"count": 230, "mean": 0.018},
                "decode_step": {"count": 800, "mean": 0.031}})
    want = 100 * (800 * 5_731_123_200 / 819e9) / (230 * 0.018 + 800 * 0.031)
    assert _reader("serve.state_need_share").read(run) == pytest.approx(want)
    assert 0 < want < 100
    # a window of decode steps alone
    run = _run({"state": STATE,
                "prefill": {"count": 0, "mean": 0.0},
                "decode_step": {"count": 800, "mean": 0.031}})
    assert _reader("serve.state_need_share").read(run) == pytest.approx(
        100 * (800 * 5_731_123_200 / 819e9) / 24.8)


def test_a_program_without_the_counter_reads_nothing():
    """The parent of PR 30 has no ``stats()["state"]``; a model of attention
    layers alone holds no state; a window may hold no program; a rehearsal
    has no peaks: None, never a raise."""
    read = _reader("serve.state_need_share").read
    hists = {"prefill": {"count": 3, "mean": 0.1},
             "decode_step": {"count": 3, "mean": 20e-3}}
    for engine in ({}, dict(hists), dict(hists, state={}),
                   dict(hists, state={"state_bytes": 0, "kv_bytes": 77}),
                   {"state": STATE},
                   {"state": STATE,
                    "prefill": {"count": 0, "mean": 0.0},
                    "decode_step": {"count": 0, "mean": 0.0}}):
        assert read(_run(engine)) is None
    assert read(types.SimpleNamespace(trace={}, peak=PEAK, counters={},
                                      window=(0.0, 30.0),
                                      ctx=types.SimpleNamespace(
                                          config=REAL))) is None
    assert read(_run(dict(hists, state=STATE), peak=None)) is None


# -- the cell through run.py --------------------------------------------------

def _rehearse(benchmark, seed="3000000019", trace="1", **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1", **env)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", benchmark,
         "--rehearse", "--workload", "tiny-kimilinear-reason", "--seed",
         seed, "--seconds", "3", "--trace", trace], cwd=spec.ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_cell_runs_through_run_py_unchanged():
    """A slot of whole state, three tails and a headless latent through
    build / warm-up / window / verifier of chipbench/drivers/serve.py as it
    is, seed above 2**31, traced: the counter metrics are read, the trace
    metrics and those that need a chip's peaks say nothing, and the served
    tokens are the reference's."""
    line, out = _rehearse(FIXTURE)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    said = dict(re.findall(r"\[chipbench\]   ([\w.]+): (\S+) ", out))
    assert said["compile.in_window"] == "0"
    assert 1.0 <= float(said["serve.moe_load_max_over_mean"]) < 8.0
    assert 1.0 <= float(said["serve.moe_rows_computed_over_held"]) < 16.0
    # three layers of state against one of latent: most of the cache bytes
    assert 50.0 < float(said["serve.state_bytes_share"]) < 100.0
    assert 0.0 < float(said["serve.latent_read_share"]) < 100.0
    assert (said["kernel.gmm_share"] == said["kernel.gmm_ep_roofline"]
            == said["serve.decode_roofline"]
            == said["kernel.mla_decode_roofline"]
            == said["serve.state_need_share"] == "None")
    # mostly decode steps: a request is a short prompt and a longer answer
    steps, prefills = re.search(
        r"mean decode step \S+ ms x (\d+), prefill \S+ ms x (\d+)", out
    ).groups()
    assert int(steps) > int(prefills)


@pytest.mark.parametrize("which, fault", [("fp8", ""),
                                          ("fault", "mean_decay"),
                                          ("fault", "silu_gate")])
def test_the_cell_ends_incorrect_under_a_control(which, fault, tmp_path):
    """The fixture benchmark with a control's directory searched first: the
    same run, judged by float8 weights or by another model's mathematics,
    ends ``"correct": false`` (on the chip at the published widths: PERF.md
    section 6, PR 40).  Not ``roped_k_pe`` here: one latent layer of four
    over forty positions moves no served token off its position's largest
    logit at this size (tests/test_kimi_linear.py sees it in the logits)."""
    bench = spec.load_benchmark(FIXTURE)
    bench["paths"] = [CONTROLS[which]] + bench["paths"]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    line, out = _rehearse(str(path), trace="0", KIMILINEAR_FAULT=fault)
    assert line["correct"] is False and line["failed"] == 0, out[-600:]
    assert line["attempted"] > 0

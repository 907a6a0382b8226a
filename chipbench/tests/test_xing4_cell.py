"""What ISSUE 38 adds to the benchmark, on the CPU: the new configuration and
mix against the contract and the catalog's published values, the counting
function of ``serve.residual_need_share`` on shapes worked out by hand and
against the program's own counter, the new reader on counters made by hand,
nothing (not an error) from a program without the counter (the parent
commit), the float8 control, and the cell end to end through run.py and
drivers/serve.py unchanged on a fixture benchmark of its own
(tests/fixture/BENCHMARK.xing4.json: a latent-attention model whose residual
is four streams, prompts in one 128 bucket).  Membership of lists is
asserted, never that an entry is the LAST (PERF.md section 7 (3))."""

import json
import os
import re
import subprocess
import sys
import types

import pytest

from chipbench import residual_need, spec

FIXTURE = "chipbench/tests/fixture/BENCHMARK.xing4.json"
CONTROL = "chipbench/tests/fixture/fp8_control_xing4/BENCHMARK.json"
BENCH = spec.load_benchmark("BENCHMARK.json")
CELL = "serve-xing4-longdocs"
REAL = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                   "xing4-29b-a4b-serve.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PUBLISHED = {  # the catalog's config of Xing4.0-29B-A4B
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
LISTED = ("serve.occupancy", "serve.prefill_share", "serve.decode_share",
          "serve.loop_host_share", "serve.loop_ahead_share",
          "serve.loop_unnamed_share", "serve.loop_offcpu_share",
          "serve.gc_share", "kernel.gmm_share", "kernel.gmm_ep_roofline",
          "serve.moe_load_max_over_mean",
          "serve.moe_rows_computed_over_held", "serve.latent_read_share",
          "serve.decode_roofline", "kernel.mla_decode_roofline",
          "serve.residual_need_share")


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


# -- the configuration and the mix -------------------------------------------

def test_every_published_key_is_in_the_file_and_only_three_are_cut():
    assert REAL["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in REAL["reduced"]:
            assert REAL["reduced_from"][key] == value and REAL["reduced_how"][
                key]
        else:
            assert REAL[key] == value, key
    # one leading dense layer (they count once) + 5 of the 38 that follow,
    # one more than the floor; an eighth of the vocabulary; EVERY expert held
    assert (REAL["num_hidden_layers"], REAL["first_k_dense_replace"]) == (6, 1)
    assert REAL["num_hidden_layers"] - REAL["first_k_dense_replace"] >= 4
    assert (REAL["n_routed_experts"], REAL["router_num_experts"],
            REAL["expert_offset"]) == (64, 64, 0)
    assert REAL["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # no width among the cuts
    assert not any(re.search(r"size|_dim|_rank|hc_mult|per_tok", key)
                   and key != "vocab_size" for key in REAL["reduced"])
    # the factory takes scalars: rope_scaling's entries repeated flat, each
    # under its published name, and held to the group the reference reads
    for key, value in PUBLISHED["rope_scaling"].items():
        if key != "type":
            assert REAL["rope_scaling_" + key] == value, key
    kw = spec.model_kwargs(REAL)
    assert all(isinstance(v, (int, float, bool, str)) for v in kw.values())
    assert (kw["num_experts"], kw["experts_held"], kw["moe_top_k"],
            kw["moe_hidden"], kw["dense_hidden"]) == (64, 64, 4, 1024, 9216)
    assert (kw["num_heads"], kw["q_lora_rank"], kw["kv_lora_rank"],
            kw["qk_nope_head_dim"], kw["qk_rope_head_dim"],
            kw["v_head_dim"]) == (32, 768, 512, 128, 64, 128)
    assert (kw["hc_mult"], kw["hc_sinkhorn_iters"], kw["hc_eps"],
            kw["mhc_h_res_clamp_min"], kw["mhc_h_res_clamp_max"]) == (
        4, 20, 1e-6, -30, 30)
    entry = spec.named(BENCH["configs"], REAL["name"], "configuration")
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"]
    assert entry["file"] == "chipbench/configs/xing4-29b-a4b-serve.json"
    assert len(entry["why"]) <= 200
    sv = REAL["serve"]
    assert 64 <= sv["slots"] <= 128 and sv["max_len"] == 4096
    assert sv["logit_tol"] > 0 and sv["logit_tol_reason"]
    assert "GiB" in sv["slots_fit"]
    assert "v5e-8" in REAL["deployment"] and "eight pipeline stages" in REAL[
        "deployment"]
    for key in ("streams_open_and_close", "sinkhorn_order", "clamp",
                "hyper_connection_initialisers",
                "hyper_connection_precision"):
        assert REAL["assumed"][key]
    assert any("multi-token-prediction layer is NOT built" in d
               for d in REAL["departures"])


def test_the_model_is_built_from_the_file_alone():
    """The factory takes the file's scalars and derives the layer kinds;
    shapes only, nothing of the 3.97B parameters is allocated."""
    import jax
    model = spec.resolve(REAL["model"]["factory"])(**spec.model_kwargs(REAL))
    assert model.layer_kinds == ["dense"] + ["moe"] * 5 and model.streams == 4
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    assert params["block0.mlp.gate"]["weight"].shape == (3584, 9216)
    assert params["block1.mlp"]["router"].shape == (3584, 64)
    assert params["block1.mlp"]["router_bias"].shape == (64,)
    assert params["block1.mlp"]["w1"].shape == (64, 3584, 1024)
    assert "shared_gate" not in params["block1.mlp"]
    attn = params["block5.attn"]
    assert attn["q_a_weight"].shape == (3584, 768)
    assert attn["q_b_weight"].shape == (768, 32 * 192)
    assert attn["kv_a_weight"].shape == (3584, 576)
    assert attn["kv_b_weight"].shape == (512, 32 * 256)
    assert attn["out_weight"].shape == (4096, 3584)
    assert params["head"]["weight"].shape == (3584, 16384)
    hc = params["block3.hc_mlp"]
    assert hc["norm_weight"].shape == (14336,)
    assert (hc["pre_weight"].shape, hc["post_weight"].shape,
            hc["res_weight"].shape) == ((14336, 4), (14336, 4), (14336, 16))
    assert sum(a.size for a in hc.values()) == 14336 * 24 + 14336 + 27
    # the cache holds 576 numbers a position a layer: no heads, no streams
    pool = jax.eval_shape(lambda: model.init_slot_cache(2, 4096))
    assert all(set(e) == {"latent"} and e["latent"].shape == (2, 576, 4096)
               for e in pool.values()) and len(pool) == 6
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == 3_970_758_276
    assert model.block0.attn.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 4.1588831 + 1) ** 2, rel=1e-6)
    # what a row moves: 12 sublayers x 14 widths
    assert model.residual_numbers_per_row() == 12 * 14 * 3584


def test_the_mix_and_the_entries_are_as_the_issue_lists_them():
    cell = spec.named(BENCH["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4-29b-a4b-serve", "longdocs-steady", 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) \
        == len(BENCH["workloads"])
    mix = spec.load_json(spec.find(BENCH, "traffic", "longdocs-steady.json"))
    assert mix["loop"] == "closed" and mix["clients_per_slot"] == 2
    assert mix["users"]
    (cls,) = mix["classes"]
    assert cls["prompt_len"] == {"dist": "uniform", "min": 2100, "max": 3900}
    assert cls["output_len"] == {"dist": "uniform", "min": 64, "max": 192}
    assert (mix["trace_from_s"], mix["trace_seconds"]) == (18, 3)
    # longdocs-closed's traffic to the number, the traced slice moved
    other = spec.load_json(spec.find(BENCH, "traffic", "longdocs-closed.json"))
    assert {k: v for k, v in mix.items() if k not in ("users", "trace_from_s")
            } == {k: v for k, v in other.items()
                  if k not in ("users", "trace_from_s")}
    # one 4,096 bucket, and the longest request fits a slot
    assert 2048 < cls["prompt_len"]["min"] and cls["prompt_len"]["max"] <= 4096
    assert cls["prompt_len"]["max"] + cls["output_len"]["max"] <= REAL[
        "serve"]["max_len"]
    assert mix["trace_from_s"] + mix["trace_seconds"] < BENCH["run_seconds"]
    metrics = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("serve_tokens_per_s",) + LISTED:
        assert CELL in metrics[name]["workloads"], name
        assert metrics[name].get("moves", name) == "serve_tokens_per_s"
    # its reader takes the dense 9,216 for the expert's width
    assert CELL not in metrics["kernel.gmm_roofline"]["workloads"]
    new = metrics["serve.residual_need_share"]
    assert new == {"name": "serve.residual_need_share", "unit": "%",
                   "better": "higher", "source": "program_counter",
                   "layer": "serve_model_step",
                   "moves": "serve_tokens_per_s", "workloads": [CELL]}
    # the readers of the shared metrics find their widths under the keys
    # the published file has
    assert {"hidden_size", "moe_intermediate_size", "num_experts_per_tok",
            "num_attention_heads", "kv_lora_rank",
            "qk_rope_head_dim"} <= set(REAL)


def test_the_float8_control_runs_the_cell_itself_under_another_reference():
    """tests/fixture/fp8_control_xing4/BENCHMARK.json: the cell's own
    entries, configuration file and mix; only the file its ``reference``
    names is found elsewhere first, and that one rounds every matrix a
    matmul reads to float8 e4m3 and nothing else, inside the plain
    reference's forward: the weakest float8 computation there is
    (PERF.md, PR 38: on the chip the run ends ``"correct": false``)."""
    import jax
    import jax.numpy as jnp
    control = spec.load_benchmark(CONTROL)
    assert control["workloads"] == [spec.named(BENCH["workloads"], CELL,
                                               "cell")]
    assert control["configs"] == [spec.named(BENCH["configs"], REAL["name"],
                                             "configuration")]
    assert control["run_seconds"] == BENCH["run_seconds"]
    assert spec.find(control, "traffic", "longdocs-steady.json") == spec.find(
        BENCH, "traffic", "longdocs-steady.json")
    plain = spec.load_module(spec.find(BENCH, "reference", REAL["reference"]))
    low = spec.load_module(spec.find(control, "reference", REAL["reference"]))
    assert low.forward.__code__.co_filename == plain.forward.__code__.co_filename
    tiny = spec.load_json(os.path.join(
        spec.ROOT, "chipbench/tests/fixture/configs/tiny-xing4-serve.json"))
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
    assert jnp.array_equal(ctl["hc_attn"]["res_weight"],
                           e4m3(own["hc_attn"]["res_weight"]))
    for name in ("res_bias", "pre_bias", "res_scale", "norm_weight"):
        assert jnp.array_equal(ctl["hc_mlp"][name], own["hc_mlp"][name]), name
    # the experts' stacks pass as they are (a rounded copy of them does not
    # fit beside the parameters on the chip) and are rounded where read
    assert ctl["mlp"]["w1"] is params["block1.mlp"]["w1"]
    g, u, d = (own["mlp"][k][0] for k in ("w1", "w3", "w2"))
    h = jnp.ones((3, g.shape[0]), jnp.float32)
    assert jnp.array_equal(low._plain.gated_mlp(g, u, d, h),
                           plain.gated_mlp(e4m3(g), e4m3(u), e4m3(d), h))
    assert not jnp.array_equal(low._plain.gated_mlp(g, u, d, h),
                               plain.gated_mlp(g, u, d, h))
    # and nothing else: the norms and the mixes are the plain reference's
    x, w = jnp.linspace(-2.0, 3.0, 48).reshape(3, 16), jnp.ones((16,))
    assert jnp.array_equal(low._plain._norm(x, w, 1e-6),
                           plain._norm(x, w, 1e-6))
    assert (low._plain.hyper_connected.__code__.co_code
            == plain.hyper_connected.__code__.co_code)


def test_the_reference_imports_nothing_of_the_program_or_of_its_siblings():
    text = open(spec.find(BENCH, "reference", REAL["reference"])).read()
    code = re.sub(r'""".*?"""', "", text, flags=re.S)
    assert "tpu_dist" not in code and "kimi_k2" not in code
    assert not re.search(r"^\s*(from|import) (?!__future__|math|jax)", code,
                         flags=re.M)
    assert 'default_matmul_precision("highest")' in text


# -- the counting function ----------------------------------------------------

# the cell by hand: 12 sublayers, 4 streams of 3,584 bfloat16; a 4,096-bucket
# prefill of 3,000 true tokens, a decode step of 96 busy slots
def test_residual_need_on_hand_computed_shapes():
    assert residual_need.numbers_per_row(4, 3584) == 14 * 3584 == 50_176
    a_row = residual_need.bytes_moved(1, 12, 4, 3584)
    assert a_row == 12 * 50_176 * 2 == 1_204_224
    # a whole 4,096 bucket: 4.9 GB, 6 ms at 819 GB/s (the issue's estimate)
    assert residual_need.bytes_moved(4096, 12, 4, 3584) == 4_932_501_504
    assert 4_932_501_504 / 819e9 == pytest.approx(6.02e-3, rel=1e-2)
    residual = {"streams": 4, "sublayers": 12,
                "prefill": {"rows": 3000, "bytes": 3000 * a_row},
                "decode": {"rows": 96, "bytes": 96 * a_row}}
    least = residual_need.least_seconds(residual, PEAK)
    assert least == pytest.approx(3096 * 1_204_224 / 819e9)
    assert residual_need.need_share(residual, 0.115, PEAK) == pytest.approx(
        100 * least / 0.115)
    assert 0 < residual_need.need_share(residual, 0.115, PEAK) < 100
    assert residual_need.need_share(residual, 0.0, PEAK) is None
    assert residual_need.need_share({}, 1.0, PEAK) is None
    plain = {"streams": 1, "sublayers": 96,
             "prefill": {"rows": 9, "bytes": 0},
             "decode": {"rows": 2, "bytes": 0}}
    assert residual_need.need_share(plain, 1.0, PEAK) is None


def test_the_programs_counter_is_the_need_functions_arithmetic():
    """``SlotEngine.stats()["residual"]`` on the fixture model against
    ``residual_need.bytes_moved`` from the configuration's shapes."""
    import jax
    from tpu_dist import serve
    tiny = spec.load_json(os.path.join(
        spec.ROOT, "chipbench/tests/fixture/configs/tiny-xing4-serve.json"))
    model = spec.resolve(tiny["model"]["factory"])(**spec.model_kwargs(tiny))
    eng = serve.SlotEngine(model, model.init(jax.random.key(0)), num_slots=2,
                           max_len=64, min_bucket=32)
    eng.admit(serve.Request(list(range(1, 20)), max_new_tokens=3))
    eng.step()
    got = eng.stats()["residual"]
    args = (2 * tiny["num_hidden_layers"], tiny["hc_mult"],
            tiny["hidden_size"], 4)
    assert (got["streams"], got["sublayers"]) == (4, 4)
    assert got["prefill"] == {"rows": 19, "bytes": residual_need.bytes_moved(19, *args)}
    assert got["decode"] == {"rows": 1, "bytes": residual_need.bytes_moved(1, *args)}


def _run(engine, peak=PEAK):
    return types.SimpleNamespace(
        trace={}, peak=peak, counters={"engine": engine},
        window=(0.0, 30.0), ctx=types.SimpleNamespace(config=REAL))


RESIDUAL = {"streams": 4, "sublayers": 12,
            "prefill": {"rows": 600_000, "bytes": 600_000 * 1_204_224},
            "decode": {"rows": 30_000, "bytes": 30_000 * 1_204_224}}


def test_the_reader():
    run = _run({"residual": RESIDUAL,
                "prefill": {"count": 200, "mean": 0.120},
                "decode_step": {"count": 400, "mean": 0.015}})
    want = 100 * (630_000 * 1_204_224 / 819e9) / (200 * 0.120 + 400 * 0.015)
    assert _reader("serve.residual_need_share").read(run) == pytest.approx(
        want)
    assert 0 < want < 100
    # a window of prefills alone
    run = _run({"residual": RESIDUAL,
                "prefill": {"count": 200, "mean": 0.120},
                "decode_step": {"count": 0, "mean": 0.0}})
    assert _reader("serve.residual_need_share").read(run) == pytest.approx(
        100 * (630_000 * 1_204_224 / 819e9) / 24.0)


def test_a_program_without_the_counter_reads_nothing():
    """The parent commit has no ``stats()["residual"]``; a plain residual
    moves no byte; a window may hold no program; a rehearsal has no peaks:
    None, never a raise."""
    read = _reader("serve.residual_need_share").read
    hists = {"prefill": {"count": 3, "mean": 0.1},
             "decode_step": {"count": 3, "mean": 20e-3}}
    zero = {"rows": 5, "bytes": 0}
    for engine in ({}, dict(hists), dict(hists, residual={}),
                   dict(hists, residual={"streams": 1, "sublayers": 96,
                                         "prefill": zero, "decode": zero}),
                   {"residual": RESIDUAL},
                   {"residual": RESIDUAL,
                    "prefill": {"count": 0, "mean": 0.0},
                    "decode_step": {"count": 0, "mean": 0.0}}):
        assert read(_run(engine)) is None
    assert read(types.SimpleNamespace(trace={}, peak=PEAK, counters={},
                                      window=(0.0, 30.0),
                                      ctx=types.SimpleNamespace(
                                          config=REAL))) is None
    assert read(_run(dict(hists, residual=RESIDUAL), peak=None)) is None


# -- the cell through run.py --------------------------------------------------

def test_the_cell_runs_through_run_py_unchanged():
    """A latent-attention model under a four-stream residual through build /
    warm-up / window / verifier of chipbench/drivers/serve.py as it is, seed
    above 2**31, traced: the counter metrics are read, the trace metrics
    and those that need a chip's peaks say nothing, and the served tokens
    are the reference's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", FIXTURE,
         "--rehearse", "--workload", "tiny-xing4-longdocs", "--seed",
         "3000000019", "--seconds", "3", "--trace", "1"], cwd=spec.ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    said = dict(re.findall(r"\[chipbench\]   ([\w.]+): (\S+) ", p.stdout))
    assert said["compile.in_window"] == "0"
    assert 1.0 <= float(said["serve.moe_load_max_over_mean"]) < 8.0
    # every expert is held: the picks computed are the picks held, but for
    # padding and block alignment
    assert 1.0 <= float(said["serve.moe_rows_computed_over_held"]) < 16.0
    assert 0.0 < float(said["serve.latent_read_share"]) < 100.0
    assert (said["kernel.gmm_share"] == said["kernel.gmm_ep_roofline"]
            == said["serve.decode_roofline"]
            == said["kernel.mla_decode_roofline"]
            == said["serve.residual_need_share"] == "None")

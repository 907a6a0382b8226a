"""What ISSUE 47 adds to the benchmark, on the CPU: the new configuration
against the contract and the catalog's published values letter for letter but
for ``reduced``, the flat keys held to what they repeat, its parameters
reckoned by hand against ``jax.eval_shape``, a slot whose entries are a
convolution tail alone in eight layers of ten, ``chipbench.conv_need`` on one
call by hand and against the program's own counter, the new reader on
counters made by hand, nothing (not an error) from a program without the
counter, the float8 and the fault controls, and the cell end to end through
run.py and drivers/serve.py unchanged on a fixture benchmark of its own
(tests/fixture/BENCHMARK.lfm2moe.json: ``conv, conv, full_attention, conv``
over two dense layers and two of 16 sigmoid-routed experts, prompts in one 32
bucket), ``correct`` true, and false under each control.  Every entry is
looked up BY NAME and membership of lists is asserted, never a position in a
list (PERF.md section 7 (3)): a later PR's appends break nothing here."""

import json
import os
import re
import subprocess
import sys
import types
from unittest import mock

import pytest

from chipbench import conv_need, flops, spec

FIXTURE = "chipbench/tests/fixture/BENCHMARK.lfm2moe.json"
CONTROLS = {"fp8": "chipbench/tests/fixture/fp8_control_lfm2moe",
            "fault": "chipbench/tests/fixture/fault_control_lfm2moe"}
BENCH = spec.load_benchmark("BENCHMARK.json")
CELL = "serve-lfm2moe-reason"
REAL = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                   "lfm2-24b-a2b-serve.json"))
TINY = spec.load_json(os.path.join(
    spec.ROOT, "chipbench/tests/fixture/configs/tiny-lfm2moe-serve.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PUBLISHED = {  # the catalog's config of LFM2-24B-A2B
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["full_attention" if i % 4 == 2 else "conv"
                    for i in range(40)],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
LISTED = ("serve.occupancy", "serve.prefill_share", "serve.decode_share",
          "serve.loop_host_share", "serve.loop_ahead_share",
          "serve.loop_unnamed_share", "serve.loop_offcpu_share",
          "serve.gc_share", "kernel.gmm_share", "kernel.gmm_ep_roofline",
          "serve.moe_load_max_over_mean",
          "serve.moe_rows_computed_over_held",
          "serve.moe_combine_rows_over_held", "serve.state_bytes_share",
          "serve.decode_roofline", "serve.kv_pool_over_held",
          "serve.conv_need_share")
NOT_LISTED = ("kernel.gmm_roofline", "kernel.mla_decode_roofline",
              "kernel.delta_step_roofline", "kernel.delta_scan_roofline",
              "serve.prefill_flash_share", "serve.residual_need_share",
              "serve.state_need_share", "serve.state_kernel_share",
              "serve.prefill_scan_kernel_share", "serve.latent_read_share")
# by hand (ISSUE 47): the two mixers, the two feed-forwards, a layer's norms
CONV = 2048 * 6144 + 2048 * 3 + 2048 * 2048
ATTENTION = 2048 * (32 + 8 + 8) * 64 + 2048 * 2048 + 2 * 64
DENSE = 3 * 2048 * 11776
EXPERTS = 64 * 3 * 2048 * 1536 + 2048 * 64 + 64
NORMS = 2 * 2048
STAGE = (8 * CONV + 2 * ATTENTION + 2 * DENSE + 8 * EXPERTS + 10 * NORMS)


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows if r["name"] == "LFM2-24B-A2B")


# -- the configuration and the mix -------------------------------------------

def test_every_published_key_is_in_the_file_and_only_the_depth_is_cut():
    row = _catalog_row()
    if row is not None:         # the table above IS the catalog's row
        assert row["config"] == PUBLISHED
        assert row["source_url"] == REAL["source"]
    assert sorted(REAL["reduced"]) == ["layer_types", "num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key in REAL["reduced"]:
            assert REAL["reduced_from"][key] == value and REAL["reduced_how"][
                key]
        else:
            assert REAL[key] == value, key
    # one stage of four: the first ten published layers, letter for letter
    assert REAL["num_hidden_layers"] * 4 == PUBLISHED["num_hidden_layers"]
    assert REAL["layer_types"] == PUBLISHED["layer_types"][:10] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    # no width, head count, expert count or width, picks, taps, dense count,
    # vocabulary or rope_theta among the cuts
    assert not any(re.search(r"size|_dim|_rank|head|expert|cache|dense|"
                             r"vocab|rope", key) for key in REAL["reduced"])
    # the harness hands a factory top-level scalars: the list repeated as
    # text and the nested theta at the top, held to what the reference reads
    assert REAL["layer_types_flat"] == ",".join(REAL["layer_types"])
    assert REAL["rope_theta"] == REAL["rope_parameters"]["rope_theta"]
    assert "layer_types_flat" in REAL["flat_keys"]
    assert "rope_theta" in REAL["flat_keys"]
    kw = spec.model_kwargs(REAL)
    assert all(isinstance(v, (int, float, bool, str)) for v in kw.values())
    assert (kw["dim"], kw["depth"], kw["num_heads"], kw["num_kv_heads"],
            kw["dense_hidden"], kw["num_dense_layers"], kw["conv_kernel"],
            kw["num_experts"], kw["moe_top_k"], kw["moe_hidden"],
            kw["vocab_size"], kw["rope_theta"]) == (
        2048, 10, 32, 8, 11776, 2, 3, 64, 4, 1536, 65536, 1000000)
    entry = spec.named(BENCH["configs"], REAL["name"], "configuration")
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"]
    assert entry["file"] == "chipbench/configs/lfm2-24b-a2b-serve.json"
    assert len(entry["why"]) <= 200
    sv = REAL["serve"]
    # never a prefill bucket's count: gmm_ep_need tells a decode call from a
    # prefill call by R == slots x picks a token
    assert sv["slots"] in (320, 192) and sv["max_len"] == 1024
    assert sv["slots"] not in (64, 128, 256, 512, 1024)
    assert sv["logit_tol"] > 0 and sv["logit_tol_reason"]
    assert "GiB" in sv["slots_fit"] and "memory_analysis" in sv["slots_fit"]
    assert "2x2" in REAL["deployment"]
    assert "four pipeline stages" in REAL["deployment"]
    for key in ("tie_word_embeddings", "expert_bias", "routed_experts",
                "weights", "max_len"):
        assert REAL["assumed"][key]
    assert REAL["departures"] and REAL["unused_keys"]
    assert "TO FILL" not in json.dumps(REAL)


def test_the_model_is_built_from_the_file_alone():
    """The factory takes the file's keys; shapes only, nothing of the 5.4B
    parameters is allocated.  Each part by hand against
    ``jax.eval_shape``, and the configuration's 5,401,307,904."""
    import jax
    import jax.numpy as jnp
    from tpu_dist import nn
    model = spec.resolve(REAL["model"]["factory"])(**spec.model_kwargs(REAL))
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    assert (CONV, ATTENTION, DENSE, EXPERTS) == (
        16_783_360, 10_485_888, 72_351_744, 604_110_912)
    assert model.mixer_kinds == REAL["layer_types"]
    assert model.layer_kinds == ["dense"] * 2 + ["moe"] * 8
    for i, kind in enumerate(REAL["layer_types"]):
        assert size(params[f"block{i}.attn"]) == (
            CONV if kind == "conv" else ATTENTION)
    ffn = lambda i: {p: v for p, v in params.items()
                     if p.startswith(f"block{i}.mlp")}
    assert size(ffn(0)) == size(ffn(1)) == DENSE
    assert all(size(ffn(i)) == EXPERTS for i in range(2, 10))
    assert STAGE == 5_132_870_400
    assert size(params) == STAGE + 2 * 65536 * 2048 + 2048 == 5_401_307_904
    conv = params["block0.attn"]
    assert {k: v.shape for k, v in conv.items()} == {
        "in_weight": (2048, 6144), "conv_weight": (2048, 3),
        "out_weight": (2048, 2048)}
    attn = params["block2.attn"]
    assert attn["qkv_weight"].shape == (2048, 3072)
    assert attn["q_norm_weight"].shape == attn["k_norm_weight"].shape == (
        64,)
    assert set(attn) == {"qkv_weight", "out_weight", "q_norm_weight",
                         "k_norm_weight"}                     # no bias
    moe = params["block9.mlp"]
    assert moe["w1"].shape == moe["w3"].shape == (64, 2048, 1536)
    assert moe["w2"].shape == (64, 1536, 2048)
    assert moe["router"].shape == (2048, 64)
    assert moe["router_bias"].shape == (64,) and "shared_w1" not in moe
    assert params["head"]["weight"].shape == (2048, 65536)
    layer = model.block9.mlp
    assert (layer.scoring, layer.selection_bias, layer.normalize_gates,
            layer.routed_scale, layer.dispatch) == (
        "sigmoid", True, True, 1, "dropless")
    assert model.block2.attn.rope_theta == 1e6
    # a slot: 4 KB a position in the two attention layers and eight tails of
    # 2 x 2,048 bfloat16 numbers, whatever the context
    pool = jax.eval_shape(
        lambda: model.init_slot_cache(2, 1024, jnp.bfloat16))
    assert len(pool) == 10
    assert {n: a.shape for n, a in pool["block2.attn"].items()} == {
        "k": (2, 8, 64, 1024), "v": (2, 8, 64, 1024)}
    assert {n: (a.shape, a.dtype) for n, a in pool["block0.attn"].items()} \
        == {"conv": ((2, 4096), jnp.bfloat16)}
    assert nn.cache.slot_bytes(pool) == (8 * 8192, 2 * 8 * 64 * 2 * 2) \
        == (65_536, 4_096)
    assert 65_536 + 4_096 * 1024 == 4_259_840
    assert model.slot_state_kernel(pool) is False
    assert model.prefill_scan_kernel(pool, 256) is False
    with nn.attention_impl("flash"):    # as a TPU backend would choose
        assert model.slot_decode_kernel(pool) is True   # G = 4, D = 64


def test_the_mix_and_the_entries_are_as_the_issue_lists_them():
    cell = spec.named(BENCH["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-serve", "reason-closed", 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) \
        == len(BENCH["workloads"])
    # the mix the benchmark had, shared with two other mixers' cells
    for other in ("serve-kimilinear-reason", "serve-falconh1-reason"):
        assert spec.named(BENCH["workloads"], other,
                          "cell")["traffic"] == cell["traffic"]
    mix = spec.load_json(spec.find(BENCH, "traffic", "reason-closed.json"))
    (cls,) = mix["classes"]
    assert cls["prompt_len"]["max"] + cls["output_len"]["max"] <= REAL[
        "serve"]["max_len"]
    assert 128 < cls["prompt_len"]["min"] and cls["prompt_len"]["max"] <= 256
    # a decode step's picks are no prefill bucket's
    assert REAL["serve"]["slots"] * REAL["num_experts_per_tok"] != 256 * REAL[
        "num_experts_per_tok"]
    metrics = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("serve_tokens_per_s",) + LISTED:
        assert CELL in metrics[name]["workloads"], name
        assert metrics[name].get("moves", name) == "serve_tokens_per_s"
    for name in NOT_LISTED:
        assert CELL not in metrics[name]["workloads"], name
    assert metrics["serve.conv_need_share"] == {
        "name": "serve.conv_need_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serve_model_step",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    # no roofline metric is this PR's: it adds no kernel
    assert not any("lfm2" in name or "shortconv" in name for name in metrics)


@pytest.mark.parametrize("which", sorted(CONTROLS))
def test_a_control_runs_the_cell_itself_under_another_reference(which):
    """tests/fixture/{fp8,fault}_control_lfm2moe/BENCHMARK.json: the cell's
    own entries, configuration file and mix; only the file its ``reference``
    names is found elsewhere first."""
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


def test_the_float8_control_rounds_the_matrices_where_they_are_read():
    """Every matrix a matmul reads rounded to float8 e4m3 inside the plain
    reference's own forward, and nothing else: the weakest float8
    computation there is."""
    import jax
    import jax.numpy as jnp
    control = spec.load_benchmark(CONTROLS["fp8"] + "/BENCHMARK.json")
    plain = spec.load_module(spec.find(BENCH, "reference", REAL["reference"]))
    low = spec.load_module(spec.find(control, "reference", REAL["reference"]))
    assert low.forward.__code__.co_filename == \
        plain.forward.__code__.co_filename
    model = spec.resolve(TINY["model"]["factory"])(**spec.model_kwargs(TINY))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          model.init(jax.random.key(0)))
    # the parameters pass as they are (a rounded copy of 10 GiB does not fit
    # beside them on the chip) ...
    a, b = plain.stack_params(TINY, params), low.stack_params(TINY, params)
    assert b["head"] is a["head"] is params["head"]["weight"]
    # ... and every matrix read goes through ``_mat``, which rounds
    e4m3 = lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    w = a["blocks"][1]["mixer"]["in_weight"]
    assert jnp.array_equal(low._plain._mat(w), e4m3(w))
    assert not jnp.array_equal(low._plain._mat(w), plain._mat(w))
    tokens = jnp.arange(24)[None] % TINY["vocab_size"]
    got, want = low.forward(TINY, b, tokens), plain.forward(TINY, a, tokens)
    assert float(jnp.abs(got - want).max()) > 1e-3
    # the same forward with the matrices rounded beforehand; the taps, the
    # norms' weights and the router's bias kept
    rounded = {path: {name: e4m3(x).astype(x.dtype)
                      if x.ndim >= 2 and name != "conv_weight" else x
                      for name, x in leaves.items()}
               for path, leaves in params.items()}
    assert jnp.array_equal(got, plain.forward(
        TINY, plain.stack_params(TINY, rounded), tokens))
    source = open(spec.find(BENCH, "reference", REAL["reference"])).read()
    assert not re.search(r"@ f32\(|@ p\[", source)      # no read around it


def test_the_fault_control_plants_one_named_fault():
    control = spec.load_benchmark(CONTROLS["fault"] + "/BENCHMARK.json")
    path = spec.find(control, "reference", REAL["reference"])
    with mock.patch.dict(os.environ, LFM2MOE_FAULT="bogus"):
        with pytest.raises(SystemExit, match="LFM2MOE_FAULT must be one"):
            spec.load_module(path)
    with mock.patch.dict(os.environ, LFM2MOE_FAULT="no_gate_in"):
        faults = spec.load_module(path).FAULTS
    assert set(faults) >= {"conv_silu", "no_gate_in", "bias_in_weights",
                           "experts_in_layer_1"}


def test_the_reference_imports_nothing_of_the_program_or_of_its_siblings():
    text = open(spec.find(BENCH, "reference", REAL["reference"])).read()
    code = re.sub(r'""".*?"""', "", text, flags=re.S)
    assert "tpu_dist" not in code and "kimi" not in code
    assert "falcon" not in code and "qwen3_next" not in code
    assert not re.search(r"^\s*(from|import) (?!__future__|jax|numpy)", code,
                         flags=re.M)
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(position" in code          # positions, not a window


# -- the counting function ----------------------------------------------------

def test_conv_need_on_one_call_by_hand():
    """One decode step of 320 busy slots and one prefill of 190 prompt tokens
    through ONE layer at the published width, bfloat16."""
    assert conv_need.layer_params(2048, 3) == CONV == 16_783_360
    step = conv_need.layer_call(320, 320, 2048, 3)
    # the three matrices once; 6 x 2,048 numbers a row; a tail of 2 x 2,048
    # numbers read and written a slot
    assert step["bytes"] == 2 * (16_783_360 + 320 * 6 * 2048
                                 + 320 * 2 * 2 * 2048) == 46_673_920
    assert step["flops"] == 2 * 320 * 16_783_360 == 10_741_350_400
    seconds, bound = flops.roofline(step["flops"], step["bytes"], PEAK)
    assert bound == "memory"
    assert seconds == pytest.approx(46_673_920 / 819e9)      # 57 us
    prefill = conv_need.layer_call(190, 1, 2048, 3)
    assert prefill["bytes"] == 2 * (16_783_360 + 190 * 6 * 2048
                                    + 2 * 2 * 2048) == 38_252_544
    assert prefill["flops"] == 2 * 190 * 16_783_360
    # a window: 900 steps of 320 rows and 600 prefills of 190, eight layers
    conv = {"layers": 8, "params": 8 * CONV,
            "decode": {"rows": 900 * 320, "calls": 900},
            "prefill": {"rows": 600 * 190, "calls": 600}}
    least = conv_need.least_seconds(conv, 2048, 3, PEAK)
    assert least == pytest.approx(8 * (900 * 46_673_920
                                       + 600 * 38_252_544) / 819e9)
    assert conv_need.need_share(conv, 2048, 3, 27.0, PEAK) == pytest.approx(
        100 * least / 27.0)
    assert 1.0 < conv_need.need_share(conv, 2048, 3, 27.0, PEAK) < 5.0
    # enough rows a call and the operations set the floor
    wide = conv_need.layer_call(4096, 1, 2048, 3)
    assert flops.roofline(wide["flops"], wide["bytes"], PEAK)[1] == "compute"


def test_the_programs_counter_is_the_need_functions_input():
    """``SlotEngine.stats()["conv"]`` on the fixture model: the layers and
    their parameters as ``conv_need`` reckons them from the configuration's
    shapes, the rows and calls of both pool programs; and
    ``stats()["state"]`` counts the tails alone."""
    import jax
    from tpu_dist import serve
    model = spec.resolve(TINY["model"]["factory"])(**spec.model_kwargs(TINY))
    eng = serve.SlotEngine(model, model.init(jax.random.key(0)), num_slots=2,
                           max_len=64, min_bucket=32)
    eng.reset_stats()
    eng.admit(serve.Request(list(range(1, 20)), max_new_tokens=4))
    for _ in range(3):
        eng.step()
    got = eng.stats()
    layers = TINY["layer_types"].count("conv")
    assert got["conv"] == {
        "layers": layers,
        "params": layers * conv_need.layer_params(TINY["hidden_size"],
                                                  TINY["conv_L_cache"]),
        "prefill": {"rows": 19, "calls": 1},
        "decode": {"rows": 3, "calls": 3}}
    # three tails of 2 x 64 float32 numbers, read and written a busy slot
    assert got["state"]["state_bytes"] == 2 * 3 * (3 * 2 * 64 * 4)
    assert got["state"]["kernel_steps"] == 0


# -- the reader ---------------------------------------------------------------

def _run(engine, config=REAL, peak=PEAK):
    return types.SimpleNamespace(
        trace={}, peak=peak, counters={"engine": engine},
        window=(0.0, 30.0), ctx=types.SimpleNamespace(config=config))


def test_the_reader():
    read = _reader("serve.conv_need_share").read
    conv = {"layers": 8, "params": 8 * CONV,
            "decode": {"rows": 900 * 320, "calls": 900},
            "prefill": {"rows": 600 * 190, "calls": 600}}
    hist = lambda mean, count: {"mean": mean, "count": count}
    engine = {"conv": conv, "prefill": hist(0.015, 600),
              "decode_step": hist(0.02, 900)}
    assert read(_run(engine)) == pytest.approx(
        conv_need.need_share(conv, 2048, 3, 27.0, PEAK))
    # a window of decode steps alone is charged those alone
    assert read(_run(dict(engine, prefill=hist(0.0, 0)))) == pytest.approx(
        conv_need.need_share(conv, 2048, 3, 18.0, PEAK))


def test_a_program_without_the_counter_reads_nothing():
    """The parent of PR 47 has no ``stats()["conv"]``, another model no such
    layer, a rehearsal no peaks, a window may hold nothing: None, never a
    raise."""
    read = _reader("serve.conv_need_share").read
    hist = {"mean": 0.02, "count": 10}
    empty = {"layers": 8, "params": 1, "decode": {"rows": 0, "calls": 0},
             "prefill": {"rows": 0, "calls": 0}}
    for engine in ({}, {"conv": {}}, {"conv": empty, "decode_step": hist},
                   {"conv": dict(empty, decode={"rows": 9, "calls": 3})}):
        assert read(_run(engine)) is None
    full = {"conv": dict(empty, decode={"rows": 9, "calls": 3}),
            "decode_step": hist}
    assert read(_run(full)) > 0
    assert read(_run(full, peak=None)) is None
    other = spec.load_json(os.path.join(
        spec.ROOT, "chipbench", "configs", "falcon-h1-34b-serve.json"))
    assert read(_run(full, config=other)) is None


# -- the cell through run.py --------------------------------------------------

def _rehearse(benchmark, seed="3000000019", trace="1", **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1", **env)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", benchmark,
         "--rehearse", "--workload", "tiny-lfm2moe-reason", "--seed",
         seed, "--seconds", "3", "--trace", trace], cwd=spec.ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_cell_runs_through_run_py_unchanged():
    """A slot whose entries are a tail alone in three layers of four through
    build / warm-up / window / verifier of chipbench/drivers/serve.py as it
    is, seed above 2**31, traced: the counter metrics are read, those that
    need a chip's peaks or a device trace say nothing, and the served tokens
    are the reference's."""
    line, out = _rehearse(FIXTURE)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    said = dict(re.findall(r"\[chipbench\]   ([\w.]+): (\S+) ", out))
    assert said["compile.in_window"] == "0"
    # three tails of 128 numbers, read and written, against 32 numbers a
    # position over a few tens of positions
    assert 10.0 < float(said["serve.state_bytes_share"]) < 90.0
    assert 1.0 <= float(said["serve.moe_load_max_over_mean"]) < 3.0
    assert float(said["serve.moe_rows_computed_over_held"]) >= 1.0
    assert 1.0 <= float(said["serve.kv_pool_over_held"]) < 1.5
    assert said["serve.decode_roofline"] == said["serve.conv_need_share"] \
        == said["kernel.gmm_ep_roofline"] == "None"
    steps, prefills = re.search(
        r"mean decode step \S+ ms x (\d+), prefill \S+ ms x (\d+)", out
    ).groups()
    assert int(steps) > int(prefills)


@pytest.mark.parametrize("which, fault", [
    ("fp8", ""), ("fault", "conv_silu"), ("fault", "no_gate_in"),
    ("fault", "not_normalized"), ("fault", "experts_in_layer_1")])
def test_the_cell_ends_incorrect_under_a_control(which, fault, tmp_path):
    """The fixture benchmark with a control's directory searched first: the
    same run, judged by float8 weights or by another model's mathematics,
    ends ``"correct": false`` (on the chip at the published widths: PERF.md
    section 6, PR 47, says which the limit sees there)."""
    bench = spec.load_benchmark(FIXTURE)
    bench["paths"] = [CONTROLS[which]] + bench["paths"]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    line, out = _rehearse(str(path), trace="0", LFM2MOE_FAULT=fault)
    assert line["correct"] is False and line["failed"] == 0, out[-600:]
    assert line["attempted"] > 0

"""What ISSUE 44 adds to the benchmark, on the CPU: the new configuration
against the contract and the catalog's published values, its parameters and
its slot reckoned by hand against ``jax.eval_shape``, the flat multiplier keys
held to the published lists, ``chipbench.state_need`` on this slot's shapes and
against the program's own counter for a layer that is a recurrent layer AND an
attention layer, the new reader on counters made by hand, nothing (not an
error) from a program without the counter, the float8 and the fault controls,
and the cell end to end through run.py and drivers/serve.py unchanged on a
fixture benchmark of its own (tests/fixture/BENCHMARK.falconh1.json: three
layers that each hold a Mamba-2 mixer and a grouped-query attention, all
fourteen multipliers away from 1, prompts in one 32 bucket), ``correct`` true,
and false under each control.  Every entry is looked up BY NAME and membership
of lists is asserted, never a position in a list (PERF.md section 7 (3)): a
later PR's appends break nothing here."""

import json
import os
import re
import subprocess
import sys
import types
from unittest import mock

import pytest

from chipbench import spec, state_need

FIXTURE = "chipbench/tests/fixture/BENCHMARK.falconh1.json"
CONTROLS = {"fp8": "chipbench/tests/fixture/fp8_control_falconh1",
            "fault": "chipbench/tests/fixture/fault_control_falconh1"}
BENCH = spec.load_benchmark("BENCHMARK.json")
CELL = "serve-falconh1-reason"
REAL = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                   "falcon-h1-34b-serve.json"))
TINY = spec.load_json(os.path.join(
    spec.ROOT, "chipbench/tests/fixture/configs/tiny-falconh1-serve.json"))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PUBLISHED = {  # the catalog's config of Falcon-H1-34B-Instruct
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}
LISTED = ("serve.occupancy", "serve.prefill_share", "serve.decode_share",
          "serve.loop_host_share", "serve.loop_ahead_share",
          "serve.loop_unnamed_share", "serve.loop_offcpu_share",
          "serve.gc_share", "serve.state_bytes_share",
          "serve.state_need_share", "serve.state_kernel_share",
          "serve.prefill_scan_kernel_share", "serve.decode_roofline",
          "serve.latent_read_share", "serve.kv_pool_over_held")
NOT_LISTED = ("kernel.gmm_share", "kernel.gmm_roofline",
              "kernel.gmm_ep_roofline", "serve.moe_load_max_over_mean",
              "serve.moe_rows_computed_over_held",
              "kernel.mla_decode_roofline", "kernel.delta_step_roofline",
              "kernel.delta_scan_roofline", "serve.prefill_flash_share",
              "serve.residual_need_share")
# a layer, by hand (ISSUE 44): the state-space mixer, the attention, the MLP
# and the two norms
W_IN = 5120 * (4096 + 4096 + 512 + 512 + 32)
SSM = W_IN + 4096 * 5120 + (5120 * 4 + 5120) + 4096 + 3 * 32
ATTENTION = 5120 * (20 + 4 + 4) * 128 + 20 * 128 * 5120
MLP = 3 * 5120 * 21504
LAYER = SSM + ATTENTION + MLP + 2 * 5120
# a slot: nine layers of float32 state and a bfloat16 tail of three inputs of
# the 5,120-channel convolution, whatever the context
SLOT_STATE = 9 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


# -- the configuration and the mix -------------------------------------------

def test_every_published_key_is_in_the_file_and_only_two_are_cut():
    assert sorted(REAL["reduced"]) == ["num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in REAL["reduced"]:
            assert REAL["reduced_from"][key] == value and REAL["reduced_how"][
                key]
        else:
            assert REAL[key] == value, key
    # one stage of eight: nine whole periods (the floor is four) and an
    # eighth of the vocabulary
    assert REAL["num_hidden_layers"] * 8 == PUBLISHED["num_hidden_layers"]
    assert REAL["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # no width, head count, group, state size or multiplier among the cuts
    assert not any(re.search(r"size|_dim|_rank|head|group|state|multiplier",
                             key) and key != "vocab_size"
                   for key in REAL["reduced"])
    # the harness hands a factory top-level scalars: the two lists repeated
    # as text, held to the lists the reference reads
    text = lambda numbers: ",".join(repr(n) for n in numbers)
    assert REAL["ssm_multipliers_flat"] == text(PUBLISHED["ssm_multipliers"])
    assert REAL["mlp_multipliers_flat"] == text(PUBLISHED["mlp_multipliers"])
    assert "ssm_multipliers_flat" in REAL["flat_keys"]
    kw = spec.model_kwargs(REAL)
    assert all(isinstance(v, (int, float, bool, str)) for v in kw.values())
    assert (kw["dim"], kw["depth"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["mlp_hidden"]) == (5120, 9, 20, 4, 128, 21504)
    assert (kw["mamba_heads"], kw["mamba_head_dim"], kw["mamba_inner_dim"],
            kw["mamba_state_dim"], kw["mamba_groups"],
            kw["mamba_conv_kernel"], kw["mamba_chunk_size"]) == (
        32, 128, 4096, 256, 2, 4, 128)
    entry = spec.named(BENCH["configs"], REAL["name"], "configuration")
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"]
    assert entry["file"] == "chipbench/configs/falcon-h1-34b-serve.json"
    assert len(entry["why"]) <= 200
    sv = REAL["serve"]
    assert 48 <= sv["slots"] <= 80 and sv["max_len"] == 1024
    assert sv["logit_tol"] > 0 and sv["logit_tol_reason"]
    assert "GiB" in sv["slots_fit"] and "memory_analysis" in sv["slots_fit"]
    assert "v5e-8" in REAL["deployment"]
    assert "eight pipeline stages" in REAL["deployment"]
    for key in ("recurrent_state_dtype", "A_log", "dt_bias", "D",
                "seeded_matrices", "weights", "max_len"):
        assert REAL["assumed"][key]
    assert REAL["departures"] and REAL["branch_counts"]
    assert "TO FILL" not in json.dumps(REAL)


def test_the_model_is_built_from_the_file_alone():
    """The factory takes the file's keys; shapes only, nothing of the 4.2B
    parameters is allocated.  The layer's 430,120,032 parameters and the
    configuration's 4,205,319,008 by hand against ``jax.eval_shape``."""
    import jax
    import jax.numpy as jnp
    from tpu_dist import nn
    model = spec.resolve(REAL["model"]["factory"])(**spec.model_kwargs(REAL))
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    size = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    layer = lambda i: {path: leaves for path, leaves in params.items()
                       if path.startswith(f"block{i}.")}
    assert (W_IN, SSM, ATTENTION, MLP) == (47_349_760, 68_351_072,
                                           31_457_280, 330_301_440)
    assert size(params["block8.attn.ssm"]) == SSM
    assert size(params["block8.attn.attention"]) == ATTENTION
    assert size(layer(0)) == size(layer(8)) == LAYER == 430_120_032
    assert size(params) == 9 * LAYER + 2 * 32640 * 5120 + 5120 \
        == 4_205_319_008
    ssm = params["block3.attn.ssm"]
    assert ssm["in_weight"].shape == (5120, 9248)
    assert ssm["conv_weight"].shape == (5120, 4)
    assert ssm["conv_bias"].shape == (5120,)
    assert ssm["A_log"].shape == ssm["dt_bias"].shape == ssm["D"].shape == (
        32,)
    assert ssm["norm_weight"].shape == (4096,)
    assert ssm["out_weight"].shape == (4096, 5120)
    attn = params["block3.attn.attention"]
    assert attn["qkv_weight"].shape == (5120, 3584)
    assert attn["out_weight"].shape == (2560, 5120)
    assert set(attn) == {"qkv_weight", "out_weight"}          # no bias
    assert params["block3.mlp.gate"]["weight"].shape == (5120, 21504)
    assert params["head"]["weight"].shape == (5120, 32640)
    mixer = model.block3.attn
    assert mixer.branches == {"attention": (1.0, 0.0375),
                              "ssm": (0.25, 0.08838834764831845)}
    assert mixer.attention.key_multiplier == PUBLISHED["key_multiplier"]
    assert mixer.attention.rope_theta == 1e11
    assert list(mixer.ssm.multipliers) == PUBLISHED["ssm_multipliers"]
    assert [model.block3.mlp.gate_multiplier,
            model.block3.mlp.down_multiplier] == PUBLISHED["mlp_multipliers"]
    assert (model.embedding_multiplier, model.head_multiplier) == (
        PUBLISHED["embedding_multiplier"], PUBLISHED["lm_head_multiplier"])
    # a slot: in EVERY layer 4 K/V heads of 128 a position and a whole state
    pool = jax.eval_shape(
        lambda: model.init_slot_cache(2, 1024, jnp.bfloat16))
    assert len(pool) == 18
    assert {n: a.shape for n, a in pool["block0.attn.attention"].items()} \
        == {"k": (2, 4, 128, 1024), "v": (2, 4, 128, 1024)}
    assert {n: (a.shape, a.dtype) for n, a in
            pool["block0.attn.ssm"].items()} == {
        "state": ((2, 32, 128, 256), jnp.float32),
        "conv": ((2, 15360), jnp.bfloat16)}
    assert nn.cache.slot_bytes(pool) == (SLOT_STATE, 9 * 4 * 128 * 2 * 2) \
        == (38_025_216, 18_432)
    assert state_need.slot_state_bytes(9, 32, 256, 128, 15360) == SLOT_STATE
    assert SLOT_STATE + 18_432 * 1024 == 56_899_584
    # neither kernel is this model's: grouped queries, a state without a
    # delta term
    assert model.slot_decode_kernel(pool) is False
    assert model.slot_state_kernel(pool) is False
    assert model.prefill_scan_kernel(pool, 256) is False


def test_the_mix_and_the_entries_are_as_the_issue_lists_them():
    cell = spec.named(BENCH["workloads"], CELL, "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "falcon-h1-34b-serve", "reason-closed", 1)
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) \
        == len(BENCH["workloads"])
    # the mix the benchmark had, shared with serve-kimilinear-reason: the
    # two cells differ by the model alone
    other = spec.named(BENCH["workloads"], "serve-kimilinear-reason", "cell")
    assert other["traffic"] == cell["traffic"]
    mix = spec.load_json(spec.find(BENCH, "traffic", "reason-closed.json"))
    (cls,) = mix["classes"]
    assert cls["prompt_len"]["max"] + cls["output_len"]["max"] <= REAL[
        "serve"]["max_len"]
    assert 128 < cls["prompt_len"]["min"] and cls["prompt_len"]["max"] <= 256
    metrics = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("serve_tokens_per_s",) + LISTED:
        assert CELL in metrics[name]["workloads"], name
        assert metrics[name].get("moves", name) == "serve_tokens_per_s"
    for name in NOT_LISTED:
        assert CELL not in metrics[name]["workloads"], name
    assert metrics["serve.kv_pool_over_held"] == {
        "name": "serve.kv_pool_over_held", "unit": "x", "better": "lower",
        "source": "program_counter", "layer": "serve_model_step",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    # no roofline metric is this PR's: it adds no kernel
    assert not any("falcon" in name or "ssd" in name or "mamba" in name
                   for name in metrics)


@pytest.mark.parametrize("which", sorted(CONTROLS))
def test_a_control_runs_the_cell_itself_under_another_reference(which):
    """tests/fixture/{fp8,fault}_control_falconh1/BENCHMARK.json: the cell's
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
    computation there is (PERF.md, PR 44: on the chip the run ends
    ``"correct": false``)."""
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
    # the parameters pass as they are (a rounded copy of 7.8 GiB does not
    # fit beside them on the chip) ...
    a, b = plain.stack_params(TINY, params), low.stack_params(TINY, params)
    assert b["head"] is a["head"] is params["head"]["weight"]
    # ... and every matrix read goes through ``_mat``, which rounds
    e4m3 = lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    w = a["blocks"][1]["ssm"]["in_weight"]
    assert jnp.array_equal(low._plain._mat(w), e4m3(w))
    assert not jnp.array_equal(low._plain._mat(w), plain._mat(w))
    tokens = jnp.arange(24)[None] % TINY["vocab_size"]
    got, want = low.forward(TINY, b, tokens), plain.forward(TINY, a, tokens)
    assert float(jnp.abs(got - want).max()) > 1e-3
    # the same forward with the matrices rounded beforehand, vectors kept
    rounded = {path: {name: e4m3(x).astype(x.dtype)
                      if x.ndim == 2 and name != "conv_weight" else x
                      for name, x in leaves.items()}
               for path, leaves in params.items()}
    assert jnp.array_equal(got, plain.forward(
        TINY, plain.stack_params(TINY, rounded), tokens))
    source = open(spec.find(BENCH, "reference", REAL["reference"])).read()
    assert not re.search(r"@ f32\(|@ p\[", source)      # no read around it


def test_the_fault_control_plants_one_named_fault():
    control = spec.load_benchmark(CONTROLS["fault"] + "/BENCHMARK.json")
    path = spec.find(control, "reference", REAL["reference"])
    with mock.patch.dict(os.environ, FALCONH1_FAULT="bogus"):
        with pytest.raises(SystemExit, match="FALCONH1_FAULT must be one"):
            spec.load_module(path)
    with mock.patch.dict(os.environ, FALCONH1_FAULT="no_ssm"):
        assert spec.load_module(path).FAULTS == (
            "no_attention", "no_ssm", "one_norm_group", "key_multiplier_1",
            "dt_no_softplus")


def test_the_reference_imports_nothing_of_the_program_or_of_its_siblings():
    text = open(spec.find(BENCH, "reference", REAL["reference"])).read()
    code = re.sub(r'""".*?"""', "", text, flags=re.S)
    assert "tpu_dist" not in code and "kimi" not in code
    assert "qwen3_next" not in code
    assert not re.search(r"^\s*(from|import) (?!__future__|jax|numpy)", code,
                         flags=re.M)
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(\n        token" in code       # positions, not chunks


# -- the counting function ----------------------------------------------------

def test_state_need_on_hand_computed_shapes():
    slot = state_need.slot_state_bytes(9, 32, 256, 128, 15360)
    assert slot == 9 * (4_194_304 + 30_720) == 38_025_216
    a_step = state_need.bytes_moved(64, slot)
    assert a_step == 2 * 64 * 38_025_216 == 4_867_227_648
    # the issue's 4.87 GB and 5.9 ms a step at 819 GB/s, at its 64 slots;
    # 6.08 GB and 7.4 ms at the cell's 80
    assert a_step / 819e9 == pytest.approx(5.94e-3, rel=1e-2)
    assert state_need.bytes_moved(REAL["serve"]["slots"], slot) / 819e9 \
        == pytest.approx(7.43e-3, rel=1e-2)
    state = {"state_bytes": 1200 * a_step, "kv_bytes": 1200 * 750_000_000}
    least = state_need.least_seconds(state, PEAK)
    assert least == pytest.approx(1200 * 4_867_227_648 / 819e9)
    assert 0 < state_need.need_share(state, 28.0, PEAK) < 100


def test_the_programs_counter_is_the_need_functions_arithmetic():
    """``SlotEngine.stats()["state"]`` on the fixture model against
    ``state_need`` from the configuration's shapes: every layer counts under
    BOTH heads."""
    import jax
    from tpu_dist import serve
    model = spec.resolve(TINY["model"]["factory"])(**spec.model_kwargs(TINY))
    eng = serve.SlotEngine(model, model.init(jax.random.key(0)), num_slots=2,
                           max_len=64, min_bucket=32)
    eng.admit(serve.Request(list(range(1, 20)), max_new_tokens=4))
    eng.reset_stats()
    for _ in range(3):
        eng.step()
    slot = state_need.slot_state_bytes(
        TINY["num_hidden_layers"], TINY["mamba_n_heads"],
        TINY["mamba_d_state"], TINY["mamba_d_head"],
        3 * (TINY["mamba_d_ssm"] + 2 * TINY["mamba_n_groups"]
             * TINY["mamba_d_state"]), tail_itemsize=4)
    got = eng.stats()
    assert got["state"]["state_bytes"] == state_need.bytes_moved(3, slot)
    # three layers of 2 K/V heads of 8, k and v, float32: 20, 21, 22 resident
    assert got["state"]["kv_bytes"] == 3 * 2 * 8 * 2 * 4 * (20 + 21 + 22)
    assert got["state"]["kernel_steps"] == 0
    assert got["decode_attn"]["kernel"] is False
    assert got["prefill_scan"]["kernel_prefills"] == 0


# -- the reader ---------------------------------------------------------------

def _run(engine):
    return types.SimpleNamespace(
        trace={}, peak=PEAK, counters={"engine": engine},
        window=(0.0, 30.0), ctx=types.SimpleNamespace(config=REAL))


def test_the_reader():
    """A pool of 64 slots of 8 blocks of 128 columns a step; the busy ones
    hold 3 to 8 blocks each."""
    read = _reader("serve.kv_pool_over_held").read
    attn = {"kv_blocks_read": 1200 * 64 * 5, "kv_blocks_pool": 1200 * 64 * 8,
            "steps": 1200, "block": 128, "kernel": False}
    assert read(_run({"decode_attn": attn})) == pytest.approx(1.6)
    # on the kernel the step reads the held blocks alone
    assert read(_run({"decode_attn": dict(attn, kernel=True)})) == 1.0
    # never under 1: the pool holds every block a slot can
    assert read(_run({"decode_attn": dict(
        attn, kv_blocks_read=attn["kv_blocks_pool"])})) == 1.0


def test_a_program_without_the_counter_reads_nothing():
    """The parent of PR 24 has no ``stats()["decode_attn"]``; a window may
    hold no decode step: None, never a raise."""
    read = _reader("serve.kv_pool_over_held").read
    for engine in ({}, {"decode_attn": {}},
                   {"decode_attn": {"kv_blocks_read": 0, "kv_blocks_pool": 0,
                                    "steps": 0, "kernel": False}}):
        assert read(_run(engine)) is None
    assert read(types.SimpleNamespace(trace={}, peak=None, counters={},
                                      window=(0.0, 30.0))) is None


# -- the cell through run.py --------------------------------------------------

def _rehearse(benchmark, seed="3000000019", trace="1", **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1", **env)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", benchmark,
         "--rehearse", "--workload", "tiny-falconh1-reason", "--seed",
         seed, "--seconds", "3", "--trace", trace], cwd=spec.ROOT,
        env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_the_cell_runs_through_run_py_unchanged():
    """A slot of K/V columns and a whole state in every layer through build /
    warm-up / window / verifier of chipbench/drivers/serve.py as it is, seed
    above 2**31, traced: the counter metrics are read, those that need a
    chip's peaks say nothing, and the served tokens are the reference's."""
    line, out = _rehearse(FIXTURE)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    said = dict(re.findall(r"\[chipbench\]   ([\w.]+): (\S+) ", out))
    assert said["compile.in_window"] == "0"
    # a float32 state of 4 x 8 x 16 a layer against 32 numbers a position
    assert 30.0 < float(said["serve.state_bytes_share"]) < 100.0
    assert 0.0 < float(said["serve.latent_read_share"]) < 100.0
    assert float(said["serve.state_kernel_share"]) == 0.0
    assert float(said["serve.prefill_scan_kernel_share"]) == 0.0
    # max_len 128 is one block of 128 columns a slot: the pool over the held
    # is slots over busy slots
    assert 1.0 <= float(said["serve.kv_pool_over_held"]) < 1.5
    assert said["serve.decode_roofline"] == said["serve.state_need_share"] \
        == "None"
    steps, prefills = re.search(
        r"mean decode step \S+ ms x (\d+), prefill \S+ ms x (\d+)", out
    ).groups()
    assert int(steps) > int(prefills)


@pytest.mark.parametrize("which, fault", [
    ("fp8", ""), ("fault", "no_attention"), ("fault", "no_ssm"),
    ("fault", "one_norm_group"), ("fault", "key_multiplier_1"),
    ("fault", "dt_no_softplus")])
def test_the_cell_ends_incorrect_under_a_control(which, fault, tmp_path):
    """The fixture benchmark with a control's directory searched first: the
    same run, judged by float8 weights or by another model's mathematics,
    ends ``"correct": false`` (on the chip at the published widths: PERF.md
    section 6, PR 44)."""
    bench = spec.load_benchmark(FIXTURE)
    bench["paths"] = [CONTROLS[which]] + bench["paths"]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    line, out = _rehearse(str(path), trace="0", FALCONH1_FAULT=fault)
    assert line["correct"] is False and line["failed"] == 0, out[-600:]
    assert line["attempted"] > 0

"""What ISSUE 25 adds to the benchmark, on the CPU: the plain OLMoE reference
against the program at a tiny size, the counting functions of
``kernel.gmm_roofline`` on shapes worked out by hand, the three new readers on
a reduced trace made by hand, nothing (not an error) from a program without
the counters, and the cell end to end through run.py unchanged on a fixture
benchmark of its own (tests/fixture/BENCHMARK.olmoe.json)."""

import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import gmm_need, spec

FIXTURE = "chipbench/tests/fixture/BENCHMARK.olmoe.json"
BENCH = spec.load_benchmark("BENCHMARK.json")
CFG = spec.load_json(spec.find({"paths": ["chipbench/tests/fixture"]},
                               "configs", "tiny-olmoe-serve.json"))
REAL = spec.load_json(os.path.join(spec.ROOT, "chipbench", "configs",
                                   "olmoe-1b-7b-serve.json"))
REF = spec.load_module(spec.find({"paths": ["chipbench"]}, "reference",
                                 CFG["reference"]))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


# -- the reference ------------------------------------------------------------

@pytest.fixture(scope="module")
def program():
    model = spec.resolve(CFG["model"]["factory"])(**spec.model_kwargs(CFG))
    params = model.init(jax.random.key(3))
    # norm weights start at one, the head's bias at zero: a wrong mapping of
    # any vector must show
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim == 1 else a, params)
    return model, params


def test_norm_eps_and_keywords_are_the_published_ones(program):
    from tpu_dist import nn
    model, _ = program
    assert model.ln_f.eps == model.block0.ln1.eps == CFG["rms_norm_eps"]
    assert model.block0.attn.qk_norm_eps == CFG["rms_norm_eps"]
    assert isinstance(model.block1.mlp, nn.MoELayer)
    assert model.block1.mlp.gated and not model.block1.mlp.normalize_gates
    # the full configuration spells the published keys and cuts only depth
    kw = spec.model_kwargs(REAL)
    assert (kw["dim"], kw["num_heads"], kw["num_experts"], kw["moe_top_k"],
            kw["moe_hidden"], kw["vocab_size"]) == (2048, 16, 64, 8, 1024,
                                                    50304)
    assert kw["moe_normalize_gates"] is False and kw["attn_bias"] is False
    assert REAL["reduced"] == ["num_hidden_layers"] and kw["depth"] == 12


def test_logits_match_the_program(program):
    model, params = program
    tokens = np.random.default_rng(0).integers(0, CFG["vocab_size"], (2, 40))
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, jnp.asarray(tokens))
    stacked = REF.stack_params(CFG, params)
    got = REF.forward(CFG, stacked, jnp.asarray(tokens))
    assert got.dtype == jnp.float32 and got.shape == want.shape
    # same mathematics in float32: only the order of sums differs
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    # nothing is copied: the regrouped tree holds the program's own arrays
    assert stacked["blocks"][0]["gate"] is params["block0.mlp"]["w1"]


def test_served_tokens_are_the_reference_argmax(program):
    """Prefill and decode through the slot cache agree with the reference's
    full forward, token by token."""
    model, params = program
    prompt = np.random.default_rng(2).integers(0, CFG["vocab_size"], 9)
    out = np.asarray(jax.jit(model.generate, static_argnums=(2,))(
        params, jnp.asarray(prompt)[None], 6))[0]
    logits = REF.forward(CFG, REF.stack_params(CFG, params),
                         jnp.asarray(out)[None])[0]
    for j in range(6):
        row = logits[len(prompt) - 1 + j]
        assert float(row.max() - row[out[len(prompt) + j]]) <= 1e-4


# -- the counting functions ---------------------------------------------------

def test_grouped_matmul_need_on_hand_computed_shapes():
    # a 1024-token prefill: 8192 routed rows, all 64 experts reached
    n = gmm_need.grouped_matmul(8192, 64, 2048, 1024)
    assert n["flops"] == 2 * 8192 * 2048 * 1024 == 34_359_738_368
    assert n["bytes"] == (64 * 2048 * 1024 + 8192 * 3072) * 2 == 318_767_104
    # memory bound on a v5e: 0.389 ms to read, 0.174 ms to compute
    t, bound = gmm_need.flops.roofline(n["flops"], n["bytes"], PEAK)
    assert bound == "memory" and t == pytest.approx(318_767_104 / 819e9)
    # a decode step: 256 rows over 63 experts; direction does not matter
    d = gmm_need.grouped_matmul(256, 63, 1024, 2048)
    assert d["flops"] == 2 * 256 * 2048 * 1024
    assert d["bytes"] == (63 * 2048 * 1024 + 256 * 3072) * 2


def _moe(pre, dec):
    keys = ("rows", "pad_rows", "calls", "experts_hit")
    return {"rows_per_expert": [4, 2, 2, 8], "rows": 16, "pad_rows": 0,
            "calls": 0, "by_phase": {"prefill": dict(zip(keys, pre)),
                                     "decode": dict(zip(keys, dec))}}


def test_roofline_share_from_rows_made_by_hand():
    ms = 1_000_000      # rows are in ns
    reduced = {"rows0": [
        ("gmm_r8192.3 bf16[16384,1024]", 0, 1 * ms),
        ("gmm_r8192.4 bf16[16384,2048]", 1 * ms, 2 * ms),
        ("gmm_r256.1 bf16[1280,1024]", 2 * ms, 3 * ms),
        ("fusion.7 bf16[16384,2048] gmm_r8192.3", 3 * ms, 9 * ms)]}
    assert gmm_need.calls(reduced) == [(8192, 1e-3), (8192, 1e-3),
                                       (256, 1e-3)]
    # prefill: 3/4 of the routed rows are a request's, 60 experts a call;
    # decode: every row, 50 experts a call
    moe = _moe((6144 * 10, 2048 * 10, 10, 600), (256 * 5, 0, 5, 250))
    pre = gmm_need.grouped_matmul(6144, 60, 2048, 1024)
    dec = gmm_need.grouped_matmul(256, 50, 2048, 1024)
    least = (2 * pre["bytes"] + dec["bytes"]) / 819e9       # memory bound
    got = gmm_need.roofline_share(reduced, moe, 256, 2048, 1024, PEAK)
    assert got == pytest.approx(100 * least / 3e-3)
    assert 0 < got < 100
    # no gmm call in the trace: nothing, not zero
    assert gmm_need.roofline_share({"rows0": [("fusion.1", 0, 5)]}, moe, 256,
                                   2048, 1024, PEAK) is None


def _run(trace, moe, **ctx):
    eng = {"moe": moe} if moe else {}
    return types.SimpleNamespace(
        trace=trace, peak=PEAK, counters={"engine": eng}, window=(0.0, 30.0),
        ctx=types.SimpleNamespace(config=dict(REAL, **ctx)))


def test_the_three_readers():
    ms = 1_000_000
    trace = {"busy0_s": 8e-3, "rows0": [
        ("gmm_r8192.3 bf16[16384,1024]", 0, 2 * ms),
        ("gmm_r256.9 bf16[1280,2048]", 2 * ms, 4 * ms),
        ("fusion.2 bf16[8]", 4 * ms, 8 * ms)]}
    moe = _moe((8192, 0, 1, 64), (256, 0, 1, 64))
    run = _run(trace, moe)
    assert _reader("kernel.gmm_share").read(run) == pytest.approx(50.0)
    need = gmm_need.grouped_matmul(8192, 64, 2048, 1024)["bytes"] + \
        gmm_need.grouped_matmul(256, 64, 2048, 1024)["bytes"]
    assert _reader("kernel.gmm_roofline").read(run) == pytest.approx(
        100 * need / 819e9 / 4e-3)
    assert _reader("serve.moe_load_max_over_mean").read(run) == 2.0


@pytest.mark.parametrize("name", ["kernel.gmm_share", "kernel.gmm_roofline",
                                  "serve.moe_load_max_over_mean"])
def test_a_program_without_the_counters_or_a_trace_reads_nothing(name):
    """The parent commit has no ``stats()["moe"]`` and names no kernel
    ``gmm_r<R>``; an untraced or CPU run has no trace: None, never a raise."""
    read = _reader(name).read
    assert read(_run({}, None)) is None
    assert read(_run({"busy0_s": 1.0, "rows0": [("fusion.1", 0, 5)]},
                     None)) is None


def test_the_new_entries_are_as_the_issue_lists_them():
    cell = spec.named(BENCH["workloads"], "serve-olmoe-docs", "cell")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b-serve", "docs-closed", 1)
    metrics = {m["name"]: m for m in BENCH["per_layer"] + BENCH["end_to_end"]}
    for name in ("serve_tokens_per_s", "serve.occupancy",
                 "serve.prefill_share", "serve.decode_share",
                 "serve.loop_host_share", "kernel.gmm_share",
                 "kernel.gmm_roofline", "serve.moe_load_max_over_mean"):
        assert "serve-olmoe-docs" in metrics[name]["workloads"], name
    for name in ("kernel.gmm_share", "kernel.gmm_roofline",
                 "serve.moe_load_max_over_mean"):
        assert metrics[name]["moves"] == "serve_tokens_per_s"
    assert all(len(e["why"]) <= 200 for e in BENCH["configs"]
               + BENCH["workloads"])


# -- the cell through run.py --------------------------------------------------

def test_the_cell_runs_through_run_py_unchanged():
    """A routed model through build / warm-up / window / verifier of
    chipbench/drivers/serve.py as it is, seed above 2**31, traced: the
    counter metric is read, the trace metrics find no device and say nothing,
    and the served tokens are the reference's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", FIXTURE,
         "--rehearse", "--workload", "tiny-olmoe-docs", "--seed",
         "3000000007", "--seconds", "2", "--trace", "1"], cwd=spec.ROOT,
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    said = dict(re.findall(r"\[chipbench\]   ([\w.]+): (\S+) ", p.stdout))
    assert said["compile.in_window"] == "0"
    assert 1.0 <= float(said["serve.moe_load_max_over_mean"]) < 8.0
    assert said["kernel.gmm_share"] == said["kernel.gmm_roofline"] == "None"

"""``serve.prefill_flash_share`` (PR 39): the reader over
``SlotEngine.stats()["prefill_attn"]`` and its entry in BENCHMARK.json.
Written so that a later append breaks nothing here: the entry is found by
name, wherever it stands in its list."""

import os
import types

import pytest

from chipbench import spec

BENCH = spec.load_benchmark("BENCHMARK.json")
READ = spec.load_module(spec.find(BENCH, "layer_metrics",
                                  "serve.prefill_flash_share.py")).read


def _run(engine):
    return types.SimpleNamespace(counters={"engine": engine})


def test_the_share_is_kernel_prefills_over_prefills(capsys):
    # a Xing4.0 window as the chip served it: 275 prefills of a 4,096
    # bucket, 32 heads x 6 layers, 136 sub-tiles of 256^2 a head
    executed = 275 * 32 * 6 * 136 * 256 * 256
    attn = {"prefills": 275, "kernel_prefills": 275,
            "pairs_needed": 245_381_316_096, "pairs_executed": executed}
    assert executed == 470_600_908_800
    assert READ(_run({"prefill_attn": attn})) == 100.0
    said = capsys.readouterr().out
    assert "275 prefills, 275 on the kernel" in said
    assert "= 1.9178" in said
    assert READ(_run({"prefill_attn": dict(attn, kernel_prefills=55)})) \
        == pytest.approx(20.0)
    # the dense branch: every prefill counted, none on the kernel
    assert READ(_run({"prefill_attn": dict(attn, kernel_prefills=0)})) == 0.0


def test_a_model_without_a_latent_layer_reads_zero_and_prints_no_ratio(
        capsys):
    attn = {"prefills": 12, "kernel_prefills": 0, "pairs_needed": 0,
            "pairs_executed": 0}
    assert READ(_run({"prefill_attn": attn})) == 0.0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("engine", [
    {}, {"prefill": {"count": 3}}, {"prefill_attn": {}},
    {"prefill_attn": {"prefills": 0, "kernel_prefills": 0,
                      "pairs_needed": 0, "pairs_executed": 0}}],
    ids=["no-engine-stats", "the-parent", "empty", "no-prefill"])
def test_a_program_without_the_counter_reads_nothing(engine):
    """The parent commit has no ``stats()["prefill_attn"]``: nothing is
    reported and nothing raises; nor for a window without a prefill."""
    assert READ(_run(engine)) is None
    assert READ(types.SimpleNamespace(counters={})) is None


def test_the_reader_counts_what_the_engine_counts():
    """The engine's own counter for a latent model on the dense branch (the
    CPU's default), through the reader."""
    import jax
    import numpy as np
    from tpu_dist import serve

    cfg = spec.load_json(os.path.join(
        spec.ROOT, "chipbench/tests/fixture/configs/tiny-kimik2-serve.json"))
    model = spec.resolve(cfg["model"]["factory"])(**spec.model_kwargs(cfg))
    eng = serve.SlotEngine(model, model.init(jax.random.key(0)), num_slots=2,
                           max_len=64, min_bucket=16)
    eng.admit(serve.Request(np.arange(1, 10), max_new_tokens=2))
    attn = eng.stats()["prefill_attn"]
    layers = sum(1 for _ in model._mixers())
    heads = model.block0.attn.num_heads
    assert attn == {"prefills": 1, "kernel_prefills": 0,
                    "pairs_needed": layers * heads * 45,
                    "pairs_executed": layers * heads * 16 * 16}
    assert READ(_run({"prefill_attn": attn})) == 0.0


def test_the_entry_is_as_the_issue_lists_it():
    (m,) = [m for m in BENCH["per_layer"]
            if m["name"] == "serve.prefill_flash_share"]
    assert m == {"name": "serve.prefill_flash_share", "unit": "%",
                 "better": "higher", "source": "program_counter",
                 "layer": "kernels", "moves": "serve_tokens_per_s",
                 "workloads": ["serve-kimik2-agent", "serve-xing4-longdocs"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m["workloads"]) <= cells

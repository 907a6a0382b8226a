"""``kernel.flash_bwd_score_passes`` (PR 50): the reader over the program's
own ``backward_plan`` and ``tile_plan`` at the training cells' shapes, and its
entry in BENCHMARK.json."""

import importlib
import types

from chipbench import spec

BENCH = spec.load_benchmark("BENCHMARK.json")
READ = spec.load_module(spec.find(BENCH, "layer_metrics",
                                  "kernel.flash_bwd_score_passes.py")).read
CONFIG = spec.load_json(spec.find(BENCH, "configs", "gpt2-medium-train.json"))
# ``tpu_dist.ops.flash_attention`` the attribute is the function
fa = importlib.import_module("tpu_dist.ops.flash_attention")


def _run(seq_len=1024):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=CONFIG),
        counters={"seq_len": seq_len},
        model_kwargs=spec.model_kwargs(CONFIG))


def test_the_reader_at_the_cells_shape():
    """16 heads of 64 over 1,024 positions in bfloat16: one kernel, the 10
    sub-tiles of a causal pass computed once."""
    assert spec.model_kwargs(CONFIG)["dim"] // spec.model_kwargs(
        CONFIG)["num_heads"] == 64
    assert READ(_run()) == 1.0


def test_it_counts_as_the_program_decides(monkeypatch):
    """The pair, where the program's own estimate says a head's dQ does not
    fit: the same function ``_bwd_call`` decides with."""
    monkeypatch.setattr(fa, "_one_kernel_fits", lambda *a: False)
    assert READ(_run()) == 2.0


def test_a_program_without_the_function_reads_nothing(monkeypatch):
    monkeypatch.delattr(fa, "backward_plan")
    assert READ(_run()) is None


def test_the_entry_is_as_the_issue_lists_it():
    assert BENCH["per_layer"][-1] == {
        "name": "kernel.flash_bwd_score_passes", "unit": "ratio",
        "better": "lower", "source": "program_counter", "layer": "kernels",
        "moves": "train_tokens_per_s_per_chip",
        "workloads": ["train-gpt2m-1chip", "train-gpt2m-dp4"]}

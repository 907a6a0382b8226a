"""``serve.kv_read_share`` (PR 26): the reader over the engine's
``decode_attn`` counter, and its entry in BENCHMARK.json."""

import types

import pytest

from chipbench import spec

BENCH = spec.load_benchmark("BENCHMARK.json")
READ = spec.load_module(spec.find(BENCH, "layer_metrics",
                                  "serve.kv_read_share.py")).read


def _run(attn):
    eng = {"decode_attn": attn} if attn is not None else {}
    return types.SimpleNamespace(counters={"engine": eng})


def _attn(read, pool, kernel):
    return {"kv_blocks_read": read, "kv_blocks_pool": pool, "steps": 10,
            "block": 256, "kernel": kernel}


@pytest.mark.parametrize("attn, want", [
    (_attn(64, 1280, True), 5.0),       # chat: a few short rows
    (_attn(64, 1280, False), 100.0),    # dense branch: the whole pool
    (_attn(0, 0, True), None),          # no decode step in the window
    (None, None),                       # the parent: no such counter
], ids=["kernel", "dense", "no-steps", "no-counter"])
def test_the_reader(attn, want):
    assert READ(_run(attn)) == want


def test_no_engine_counters_reads_nothing():
    assert READ(types.SimpleNamespace(counters={})) is None


def test_the_entry_is_as_the_issue_lists_it():
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == "serve.kv_read_share"]
    assert m == {"name": "serve.kv_read_share", "unit": "%", "better": "lower",
                 "source": "program_counter", "layer": "serve_model_step",
                 "moves": "itl_p95_ms", "workloads": ["serve-gpt2xl-chat"]}
    assert BENCH["per_layer"][-1] is m

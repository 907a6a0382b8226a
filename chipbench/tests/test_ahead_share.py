"""``serve.launch_ahead_share`` and ``serve.loop_ahead_share`` (PR 29): the
readers over the engine's ``pipeline`` counter, and their entries in
BENCHMARK.json."""

import types

import pytest

from chipbench import spec

BENCH = spec.load_benchmark("BENCHMARK.json")
READ = {name: spec.load_module(spec.find(BENCH, "layer_metrics",
                                         name + ".py")).read
        for name in ("serve.launch_ahead_share", "serve.loop_ahead_share")}


def _run(pipeline):
    eng = {"pipeline": pipeline} if pipeline is not None else {}
    return types.SimpleNamespace(counters={"engine": eng})


def _pipeline(decode, prefill, ahead_decode, ahead_prefill):
    return {"launches": {"decode": decode, "prefill": prefill},
            "launched_ahead": {"decode": ahead_decode,
                               "prefill": ahead_prefill},
            "wasted_rows": 0}


@pytest.mark.parametrize("pipeline, decode_only, every", [
    (_pipeline(400, 20, 396, 5), 99.0, 100.0 * 401 / 420),   # chat
    (_pipeline(300, 500, 300, 460), 100.0, 95.0),            # docs
    (_pipeline(0, 4, 0, 1), None, 25.0),     # no decode step in the window
    (_pipeline(0, 0, 0, 0), None, None),     # an empty window
    (None, None, None),                      # the parent: no such counter
], ids=["chat", "docs", "no-steps", "empty", "no-counter"])
def test_the_readers(pipeline, decode_only, every):
    assert READ["serve.launch_ahead_share"](_run(pipeline)) == decode_only
    assert READ["serve.loop_ahead_share"](_run(pipeline)) == every


def test_no_engine_counters_read_nothing():
    for read in READ.values():
        assert read(types.SimpleNamespace(counters={})) is None


def test_the_entries_are_as_the_issue_lists_them():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    common = {"unit": "%", "better": "higher", "source": "program_counter",
              "layer": "serving_loop"}
    assert by_name["serve.launch_ahead_share"] == dict(
        common, name="serve.launch_ahead_share", moves="itl_p95_ms",
        workloads=["serve-gpt2xl-chat"])
    assert by_name["serve.loop_ahead_share"] == dict(
        common, name="serve.loop_ahead_share", moves="serve_tokens_per_s",
        workloads=["serve-gpt2xl-docs", "serve-olmoe-docs"])

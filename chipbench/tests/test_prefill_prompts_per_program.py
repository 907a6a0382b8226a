"""``serve.prefill_prompts_per_program`` (PR 48): the reader over the engine's
``pipeline`` counters, and its entry in BENCHMARK.json, looked up by name."""

import types

import pytest

from chipbench import spec

NAME = "serve.prefill_prompts_per_program"
BENCH = spec.load_benchmark("BENCHMARK.json")
READ = spec.load_module(spec.find(BENCH, "layer_metrics", NAME + ".py")).read


def _run(pipeline):
    eng = {"pipeline": pipeline} if pipeline is not None else {}
    return types.SimpleNamespace(counters={"engine": eng})


def _pipeline(prompts, programs, **more):
    return {"launches": {"decode": 900, "prefill": programs},
            "launched_ahead": {"decode": 899, "prefill": programs},
            "wasted_rows": 0, "prefill_prompts": prompts, **more}


@pytest.mark.parametrize("pipeline, want", [
    (_pipeline(920, 236, prefill_absent_rows=24), 920 / 236),   # groups
    (_pipeline(63, 63, prefill_absent_rows=0), 1.0),    # a width of one
    (_pipeline(0, 0), None),            # no prefill in the window
    ({"launches": {"decode": 900, "prefill": 736},      # the parent: no
      "launched_ahead": {"decode": 899, "prefill": 736},        # counter
      "wasted_rows": 0}, None),
    (None, None),
], ids=["groups", "width-one", "no-prefill", "parent", "no-pipeline"])
def test_the_reader(pipeline, want):
    assert READ(_run(pipeline)) == want


def test_no_engine_counters_reads_nothing():
    assert READ(types.SimpleNamespace(counters={})) is None


def test_the_entry_is_as_the_issue_lists_it():
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "x", "better": "higher",
                 "source": "program_counter", "layer": "serving_loop",
                 "moves": "serve_tokens_per_s",
                 "workloads": ["serve-lfm2moe-reason",
                               "serve-kimilinear-reason",
                               "serve-falconh1-reason", "serve-kimik2-agent",
                               "serve-olmoe-docs"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m["workloads"]) <= cells

"""The per-layer metrics that read the program's own spans (ISSUE 23): found
by name and read through run.py unchanged, on a fixture benchmark of their
own (tests/fixture/BENCHMARK.spans.json: the fixture's cells, these metrics);
their arithmetic on a table made by hand; and nothing, not an error, from a
program that has no spans."""

import os
import re
import subprocess
import sys
import types

import pytest

from chipbench import phases, spec

SPANS = "chipbench/tests/fixture/BENCHMARK.spans.json"
BENCH = spec.load_benchmark("BENCHMARK.json")
NEW = ("train.dispatch_ms_p50", "serve.decode_host_ms",
       "serve.loop_host_share")


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


def _h(count, mean):
    return {"count": count, "mean": mean, "max": mean, "p50": mean,
            "p95": mean, "p99": mean}


@pytest.mark.parametrize("cell,metric", [
    ("tiny-train-1", "train.dispatch_ms_p50"),
    ("tiny-chat", "serve.decode_host_ms"),
    ("tiny-docs", "serve.loop_host_share")])
def test_a_traced_rehearsal_prints_the_metric(cell, metric):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", SPANS,
         "--rehearse", "--workload", cell, "--seed", "5", "--seconds", "2",
         "--trace", "1"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    said = dict(re.findall(r"\[chipbench\]   ([\w.]+): (\S+) ", p.stdout))
    assert float(said[metric]) > 0
    assert int(said["fixture.phases"]) > 0      # phase_times() itself
    assert said["compile.in_window"] == "0"


def test_the_new_entries_are_appended_and_follow_the_contract():
    tail = BENCH["per_layer"][-len(NEW):]
    assert tuple(m["name"] for m in tail) == NEW
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in tail:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] == "program_span" and m["better"] == "lower"
        # reported only where the metric it moves is
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        assert m["layer"] in {x["layer"] for x in BENCH["per_layer"][:-3]}


def test_arithmetic_on_a_table_made_by_hand():
    table = {"sweep": _h(10, 0.001), "sched.wait": _h(4, 0.05),
             "stage.put": _h(3, 0.002),
             "prefill.prepare": _h(3, 0.002), "prefill.dispatch": _h(3, 0.003),
             "prefill.readback": _h(3, 0.030), "prefill.emit": _h(3, 0.001),
             "decode.dispatch": _h(10, 0.002), "decode.readback": _h(10, 0.17),
             "decode.emit": _h(10, 0.0015)}
    run = types.SimpleNamespace(counters={"engine": {"phases": table}},
                                window=(100.0, 110.0))
    # (10 x 2 ms + 10 x 1.5 ms) / 10 iterations
    assert _reader("serve.decode_host_ms").read(run) == pytest.approx(3.5)
    # 10 + 6 + 9 + 3 + 20 + 15 ms of a 10 s window; no wait, no stage thread
    assert _reader("serve.loop_host_share").read(run) == pytest.approx(0.63)
    assert phases.seconds(table, ["sched.wait"]) == pytest.approx(0.2)


def test_a_program_without_spans_reads_as_nothing(monkeypatch):
    import tpu_dist.obs
    old = types.SimpleNamespace(counters={"engine": {"decode_steps": 3}},
                                window=(0.0, 1.0))
    assert _reader("serve.decode_host_ms").read(old) is None
    assert _reader("serve.loop_host_share").read(old) is None
    empty = {n: _h(0, 0.0) for n in ("decode.dispatch", "decode.emit")}
    idle = types.SimpleNamespace(counters={"engine": {"phases": empty}},
                                 window=(0.0, 1.0))
    assert _reader("serve.decode_host_ms").read(idle) is None
    monkeypatch.delattr(tpu_dist.obs, "phase_times")
    assert phases.process(["train.dispatch"]) is None
    assert _reader("train.dispatch_ms_p50").read(old) is None

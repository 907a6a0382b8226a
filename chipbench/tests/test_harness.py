"""run.py end to end on the CPU, through ``--rehearse``, on a tiny fixture
that is NOT in BENCHMARK.json: a configuration, four mixes and a per-layer
metric added as new files under chipbench/tests/fixture and run through
run.py unchanged, which is how a later PR adds a cell.

A rehearsal prints the contract's line with empty ``metrics`` and the device
named ``cpu``; never a device metric.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

FIXTURE = "chipbench/tests/fixture/BENCHMARK.fixture.json"
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(args, devices=1, cwd=spec.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, "-m", "chipbench.run", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _cell(name, trace, devices=1):
    p = _run(["--benchmark", FIXTURE, "--rehearse", "--workload", name,
              "--seed", "5", "--seconds", "2", "--trace", str(trace)], devices)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) - {"breakdown"} == KEYS
    assert line["metrics"] == {}                  # a CPU run names no metric
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    return line, p.stdout


@pytest.mark.parametrize("cell,trace,devices", [
    ("tiny-train-1", 0, 1), ("tiny-train-4", 1, 4),
    ("tiny-chat", 1, 1), ("tiny-docs", 0, 1)])
def test_fixture_cell_runs_through_run_py_unchanged(cell, trace, devices):
    _, out = _cell(cell, trace, devices)
    assert "programs compiled or loaded inside it: 0" in out
    if trace:
        # the fixture's own per-layer reader was found by name and read
        assert "fixture.attempted:" in out and "compile.in_window: 0" in out
    else:
        assert "setup_s:" in out


def test_dp_path_shards_the_batch_over_four_devices():
    _, out = _cell("tiny-train-4", 0, 4)
    assert "steps of 8 x 32 tokens" in out and "on 4 chip(s)" in out


def test_a_cell_that_needs_four_chips_refuses_one():
    p = _run(["--benchmark", FIXTURE, "--rehearse", "--workload",
              "tiny-train-4", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and "needs 4 chips" in p.stderr
    assert not p.stdout.strip().startswith("{")


@pytest.mark.parametrize("cell", [w["name"] for w in spec.load_benchmark(
    "BENCHMARK.json")["workloads"]])
def test_without_rehearse_a_cpu_run_fails_with_no_result(cell):
    p = _run(["--workload", cell, "--seed", "1", "--seconds", "1",
              "--trace", "0"], devices=4)
    assert p.returncode != 0
    assert "measures a TPU and nothing else" in p.stderr
    assert "{" not in p.stdout


def test_fails_where_only_the_benchmark_is_present(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "train-gpt2m-1chip", "--seed", "1", "--seconds",
              "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0 and "{" not in p.stdout

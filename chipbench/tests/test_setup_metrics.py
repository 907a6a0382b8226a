"""The four ``setup.*`` metrics that read the program's compile ledger
(ISSUE 49, tpu_dist.obs.compiles): their entries in BENCHMARK.json looked up
BY NAME; read through run.py unchanged on a fixture benchmark of their own
(tests/fixture/BENCHMARK.setup.json: the fixture's cells, these metrics);
their cut at the window's first instant; and nothing, not an error, from a
program that has no ledger."""

import os
import re
import subprocess
import sys
import time
import types

import pytest

from chipbench import compiles, spec

SETUP = "chipbench/tests/fixture/BENCHMARK.setup.json"
BENCH = spec.load_benchmark("BENCHMARK.json")
NEW = {"setup.trace_lower_s": "s", "setup.compile_load_s": "s",
       "setup.cache_misses": "programs", "setup.programs": "programs"}


def _reader(name):
    return spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name", NEW)
def test_the_entry_is_as_the_issue_lists_it(name):
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(cells) >= 11
    assert m == {"name": name, "unit": NEW[name], "better": "lower",
                 "source": "program_counter", "layer": "compile",
                 "moves": "setup_s", "workloads": m["workloads"]}
    # every cell reports setup_s, so every cell is listed, by name
    assert sorted(m["workloads"]) == sorted(cells)
    (old,) = [m for m in BENCH["per_layer"] if m["name"] == "compile.in_window"]
    assert old["layer"] == m["layer"] and "workloads" not in old


@pytest.mark.parametrize("cell", ["tiny-train-1", "tiny-docs"])
def test_a_traced_rehearsal_prints_the_four_and_the_longest_programs(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--benchmark", SETUP,
         "--rehearse", "--workload", cell, "--seed", "2500000011",
         "--seconds", "2", "--trace", "1"], cwd=spec.ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    said = dict(re.findall(r"\[chipbench\]   ([\w.]+): (\S+) ", p.stdout))
    assert said["compile.in_window"] == "0"
    assert float(said["setup.trace_lower_s"]) > 0
    assert float(said["setup.compile_load_s"]) > 0
    programs, misses = (int(said["setup.programs"]),
                        int(said["setup.cache_misses"]))
    assert programs >= 2 and 0 <= misses <= programs
    setup_s = float(re.search(r"set-up ([\d.]+) s;", p.stdout).group(1))
    assert (float(said["setup.trace_lower_s"])
            + float(said["setup.compile_load_s"])) <= setup_s
    # the ledger, printed once: totals, phases, the directory, the longest
    assert len(re.findall(r"set-up built (\d+) programs", p.stdout)) == 1
    assert re.search(r"set-up built %d programs" % programs, p.stdout)
    assert "compile cache " in p.stdout and " entries (cap " in p.stdout
    step = "local_step" if cell.startswith("tiny-train") else "prefill"
    assert re.search(r"\]     %s: trace [\d.]+ s \(inner [\d.]+\), lower "
                     r"[\d.]+ s, compile or load [\d.]+ s, cache "
                     r"(hit|miss|off)" % step, p.stdout), p.stdout[-3000:]
    phase = ("setup.init_state" if cell.startswith("tiny-train")
             else "setup.build_programs")
    assert re.search(r"phases: .*%s [\d.]+ s x 1" % phase, p.stdout)


def test_the_readers_cut_at_the_windows_first_instant():
    import jax
    import jax.numpy as jnp
    from tpu_dist.utils import ensure_compile_cache
    ensure_compile_cache()

    def fresh(name):
        def f(x):
            return x * 3 + 1
        f.__name__ = f.__qualname__ = name
        return jax.jit(f)

    x = jnp.ones(3)
    fresh("cb_in_setup")(x)
    t0 = time.monotonic()
    fresh("cb_in_window")(x)
    run = types.SimpleNamespace(window=(t0, t0 + 30.0), counters={})
    ledger = compiles.setup(run)
    names = [r["name"] for r in ledger["records"]]
    assert "cb_in_setup" in names and "cb_in_window" not in names
    assert compiles.setup(run) is ledger            # read once a run
    (r,) = [r for r in ledger["records"] if r["name"] == "cb_in_setup"]
    assert all(rec["at"] < t0 for rec in ledger["records"])
    got = {n: _reader(n).read(run) for n in NEW}
    assert got["setup.programs"] == ledger["programs"] == len(names)
    assert got["setup.trace_lower_s"] == pytest.approx(
        sum(rec["trace_s"] + rec["lower_s"] for rec in ledger["records"]))
    assert got["setup.trace_lower_s"] >= r["trace_s"] + r["lower_s"] > 0
    assert got["setup.compile_load_s"] == pytest.approx(
        sum(rec["backend_s"] for rec in ledger["records"]))
    assert got["setup.cache_misses"] == sum(
        rec["cache"] == "miss" for rec in ledger["records"])
    # a window that begins before anything was built: nothing in set-up
    early = types.SimpleNamespace(window=(0.0, 1.0), counters={})
    assert _reader("setup.programs").read(early) == 0
    assert _reader("setup.trace_lower_s").read(early) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_a_ledger_reads_as_nothing(name, monkeypatch):
    import tpu_dist.obs
    monkeypatch.delattr(tpu_dist.obs, "compiles")
    old = types.SimpleNamespace(window=(0.0, 1.0), counters={})
    assert compiles.setup(old) is None
    assert _reader(name).read(old) is None

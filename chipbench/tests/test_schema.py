"""BENCHMARK.json and every file under configs/, traffic/, layer_metrics/
against the contract, so a file outside it is caught before a chip run."""

import glob
import os
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|head_dim|n_embd|n_inner|expansion|"
                   r"experts_per_tok)")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _e2e_of(cell):
    return {m["name"] for m in BENCH["end_to_end"] if spec.applies(m, cell)}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 65536
    assert 1 <= len(BENCH["command"]) <= 32
    assert not any(a.startswith("/") or ".." in a for a in BENCH["command"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PLAIN_PATH.match(p) for p in BENCH["paths"])
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_names_are_plain_and_used_once():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BENCH[key]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 0 < len(e["why"]) <= 200, e["name"]
    for root in BENCH["paths"]:
        for path in glob.glob(os.path.join(spec.ROOT, root, "**"),
                              recursive=True):
            rel = os.path.relpath(path, spec.ROOT)
            if "__pycache__" not in rel:
                assert PLAIN_PATH.match(rel), rel


def test_cells_configs_and_chips():
    configs = {c["name"]: c for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["source"].startswith("https://")
        assert not any(WIDTH.search(k) for k in c["reduced"])


def test_metrics_follow_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert "bound" not in m
        assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
        assert m["moves"] in e2e and LAYER.match(m["layer"]), m["name"]
        assert m["unit"] == "%" or not m["name"].endswith("_roofline")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert m.get("workloads", CELLS), m["name"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    assert len(_e2e_of(cell)) >= 2 and "setup_s" in _e2e_of(cell)
    layer = [m for m in BENCH["per_layer"] if spec.applies(m, cell)]
    assert layer
    # a per-layer metric is reported only where the metric it moves is
    assert all(m["moves"] in _e2e_of(cell) for m in layer)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(entry):
    cfg = spec.load_json(os.path.join(spec.ROOT, entry["file"]))
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for key in ("assumed", "departures", "deployment", "driver", "model",
                "reference"):
        assert cfg[key], key
    kw = spec.model_kwargs(cfg)
    assert all(isinstance(v, (int, float, bool, str)) for v in kw.values())
    assert callable(spec.resolve(cfg["model"]["factory"]))
    assert os.path.isfile(spec.find(BENCH, "reference", cfg["reference"]))
    assert os.path.isfile(os.path.join(spec.ROOT, "chipbench", "drivers",
                                       cfg["driver"] + ".py"))
    part = cfg.get("train") or cfg["serve"]
    tol = [k for k in part if k.endswith("_tol")]
    assert tol and all(part[k] > 0 and part[k + "_reason"] for k in tol)
    fitted = [k for k in part if k.endswith("_fit")]
    assert fitted and all("GiB" in part[k] or "GB" in part[k] for k in fitted)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_traffic_file(cell):
    mix = spec.load_json(spec.find(BENCH, "traffic", cell["traffic"] + ".json"))
    assert mix["users"]
    if mix["kind"] == "lm_batches":
        assert {"seq_len", "warmup_steps", "block_every", "trace_from_step",
                "trace_steps"} <= set(mix)
    else:
        assert mix["kind"] == "requests" and mix["loop"] in ("open", "closed")
        assert {"classes", "trace_from_s", "trace_seconds"} <= set(mix)
        need = ({"rate_per_s", "rate_reason", "drain_s"}
                if mix["loop"] == "open" else {"clients_per_slot"})
        assert need <= set(mix)
        assert mix["trace_from_s"] + mix["trace_seconds"] < BENCH["run_seconds"]
        for c in mix["classes"]:
            assert c["prompt_len"]["dist"] and c["output_len"]["dist"]


def test_every_per_layer_metric_has_its_reader_and_no_reader_is_orphaned():
    listed = {m["name"] for m in BENCH["per_layer"]}
    for name in listed:
        mod = spec.load_module(spec.find(BENCH, "layer_metrics", name + ".py"))
        assert callable(mod.read) and mod.__doc__, name
    on_disk = {os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
        spec.ROOT, "chipbench", "layer_metrics", "*.py"))}
    assert on_disk == listed


def test_nothing_branches_on_a_cell_or_configuration_name():
    names = CELLS + [c["name"] for c in BENCH["configs"]]
    code = [p for p in glob.glob(os.path.join(spec.ROOT, "chipbench", "**",
                                              "*.py"), recursive=True)
            if os.sep + "tests" + os.sep not in p]
    for path in code:
        text = open(path).read()
        assert not any(n in text for n in names), path

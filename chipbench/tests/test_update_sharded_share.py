"""``train.update_sharded_share`` (PR 37): the reader over the program's own
``shard_axis`` and the configuration's parameter shapes, and its entry in
BENCHMARK.json."""

import types

import pytest

from chipbench import spec

BENCH = spec.load_benchmark("BENCHMARK.json")
READ = spec.load_module(spec.find(BENCH, "layer_metrics",
                                  "train.update_sharded_share.py")).read
CONFIG = spec.load_json(spec.find(BENCH, "configs", "gpt2-medium-train.json"))


def _run(chips):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(config=CONFIG, chips=chips),
        model_kwargs=spec.model_kwargs(CONFIG))


@pytest.mark.parametrize("chips, want", [
    (1, 0.0),                                        # nothing to divide over
    (4, 100.0 * 405_964_800 / 406_336_593),          # the 99 matrices
], ids=["1chip", "dp4"])
def test_the_reader(chips, want):
    assert READ(_run(chips)) == pytest.approx(want, abs=1e-9)


def test_it_counts_as_the_program_does():
    """The share is ``update_plan()``'s: same function, same shapes."""
    from tpu_dist.parallel import ddp
    model = spec.resolve(CONFIG["model"]["factory"])(
        **spec.model_kwargs(CONFIG))
    group = types.SimpleNamespace(size=lambda: 4, axis_name="data", mesh=None)
    plan = ddp.DistributedDataParallel(model, group=group).update_plan()
    assert READ(_run(4)) == pytest.approx(
        100.0 * plan["sharded_elements"]
        / (plan["sharded_elements"] + plan["whole_elements"]))
    assert (plan["sharded_leaves"], plan["whole_leaves"]) == (99, 195)


def test_a_program_without_the_function_reads_nothing(monkeypatch):
    from tpu_dist.parallel import ddp
    monkeypatch.delattr(ddp, "shard_axis")
    assert READ(_run(4)) is None


def test_the_entry_is_as_the_issue_lists_it():
    (m,) = [m for m in BENCH["per_layer"]
            if m["name"] == "train.update_sharded_share"]
    assert m == {"name": "train.update_sharded_share", "unit": "%",
                 "better": "higher", "source": "program_counter",
                 "layer": "trainer_step",
                 "moves": "train_tokens_per_s_per_chip",
                 "workloads": ["train-gpt2m-1chip", "train-gpt2m-dp4"]}

"""``kernel.delta_step_roofline`` and ``serve.state_kernel_share`` (PR 41):
the need of one ``delta_step`` call by hand, the readers over a reduced
trace and ``SlotEngine.stats()``, and their entries in BENCHMARK.json.
Written so that a later append breaks nothing here: each entry is found by
name, wherever it stands in its list."""

import types

import pytest

from chipbench import delta_step_need, spec

BENCH = spec.load_benchmark("BENCHMARK.json")
load = lambda name: spec.load_module(
    spec.find(BENCH, "layer_metrics", name)).read
ROOFLINE = load("kernel.delta_step_roofline.py")
SHARE = load("serve.state_kernel_share.py")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
KIMI = spec.load_json(spec.find(BENCH, "configs",
                                "kimi-linear-48b-a3b-serve.json"))


def test_the_need_of_one_call_by_hand():
    # 120 slots x 32 heads of 128 x 128 float32, a decay a channel
    one = delta_step_need.call(120, 32, 128, 128)
    state = 2 * 120 * 32 * 128 * 128 * 4
    assert state == 503_316_480
    # q, k, g of 128, v and o of 128, beta: 641 float32 a head a row
    assert one["bytes"] == state + 120 * 32 * 641 * 4 == 513_162_240
    assert one["flops"] == 7 * 120 * 32 * 128 * 128 == 440_401_920
    # a decay a head: g is one number
    assert delta_step_need.call(96, 32, 128, 128, per_channel=False)[
        "bytes"] == 2 * 96 * 32 * 128 * 128 * 4 + 96 * 32 * 514 * 4
    # memory bound by two orders: 0.627 ms against 2.2 us
    assert one["bytes"] / 819e9 > 100 * one["flops"] / 197e12


def _trace(*rows):
    return {"rows0": [(name, s, e) for name, s, e in rows]}


NEED = {"steps": 10, "rows": 1200, "positions": 600000}


def test_the_share_is_least_seconds_over_traced_seconds():
    # two calls of 0.85 ms each over 120 busy rows a step
    trace = _trace(("delta_step.4 (f32[120,32,128], ...)", 0, 850_000),
                   ("multiply_add_fusion", 850_000, 900_000),
                   ("delta_step.5", 900_000, 1_750_000))
    least = 513_162_240 / 819e9
    got = delta_step_need.roofline_share(trace, NEED, 32, 128, 128, PEAK)
    assert got == pytest.approx(100 * 2 * least / 1.7e-3)
    assert 73 < got < 74
    run = types.SimpleNamespace(
        trace=trace, peak=PEAK, ctx=types.SimpleNamespace(config=KIMI),
        counters={"engine": {"decode_need": NEED}})
    assert ROOFLINE(run) == pytest.approx(got)


@pytest.mark.parametrize("trace, need", [
    (_trace(("multiply_add_fusion", 0, 800_000)), NEED),
    (_trace(), NEED),
    (_trace(("delta_step.4", 0, 850_000)), {}),
    (_trace(("delta_step.4", 0, 850_000)), dict(NEED, steps=0)),
], ids=["no-row", "empty", "no-counter", "no-step"])
def test_nothing_to_read_reads_nothing(trace, need):
    """The parent's trace holds no ``delta_step`` row, a slice of prefills
    alone neither: nothing is reported and nothing raises."""
    assert delta_step_need.roofline_share(trace, need, 32, 128, 128,
                                          PEAK) is None
    run = types.SimpleNamespace(
        trace=trace, peak=PEAK, ctx=types.SimpleNamespace(config=KIMI),
        counters={"engine": {"decode_need": need} if need else {}})
    assert ROOFLINE(run) is None


def test_the_roofline_reader_needs_a_trace_a_peak_and_the_layers_keys():
    trace = _trace(("delta_step.4", 0, 850_000))
    run = lambda **over: types.SimpleNamespace(**dict(dict(
        trace=trace, peak=PEAK, ctx=types.SimpleNamespace(config=KIMI),
        counters={"engine": {"decode_need": NEED}}), **over))
    assert ROOFLINE(run()) is not None
    assert ROOFLINE(run(trace=None)) is None
    assert ROOFLINE(run(peak=None)) is None
    assert ROOFLINE(run(counters={})) is None
    assert ROOFLINE(run(ctx=types.SimpleNamespace(
        config={"hidden_size": 1600}))) is None


def _stats(state):
    return types.SimpleNamespace(counters={"engine": {"state": state}})


def test_the_share_is_kernel_steps_over_steps():
    state = {"state_bytes": 1, "kv_bytes": 1, "steps": 1035,
             "kernel_steps": 1035}
    assert SHARE(_stats(state)) == 100.0
    assert SHARE(_stats(dict(state, kernel_steps=0))) == 0.0
    assert SHARE(_stats(dict(state, kernel_steps=207))) == pytest.approx(20.0)


@pytest.mark.parametrize("state", [
    None, {}, {"state_bytes": 5, "kv_bytes": 7},
    {"state_bytes": 0, "kv_bytes": 0, "steps": 0, "kernel_steps": 0}],
    ids=["no-state", "empty", "the-parent", "no-step"])
def test_a_program_without_the_counter_reads_nothing(state):
    """The parent commit's ``stats()["state"]`` has no ``steps``: nothing is
    reported and nothing raises; nor for a window without a decode step."""
    assert SHARE(_stats(state)) is None
    assert SHARE(types.SimpleNamespace(counters={})) is None


@pytest.mark.parametrize("name, source, workloads", [
    ("serve.state_kernel_share", "program_counter",
     ["serve-kimilinear-reason", "serve-qwen3next-longdocs"]),
    ("kernel.delta_step_roofline", "device_trace",
     ["serve-kimilinear-reason"])])
def test_the_entries_are_as_the_issue_lists_them(name, source, workloads):
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert m == {"name": name, "unit": "%", "better": "higher",
                 "source": source, "layer": "kernels",
                 "moves": "serve_tokens_per_s", "workloads": workloads}
    assert set(workloads) <= {w["name"] for w in BENCH["workloads"]}

"""``kernel.delta_scan_roofline`` and ``serve.prefill_scan_kernel_share``
(PR 42): the need of one ``delta_scan`` call by hand, the readers over a
reduced trace and ``SlotEngine.stats()``, and their entries in
BENCHMARK.json.  Each entry is found by name, wherever it stands in its
list, so a later append breaks nothing here."""

import types

import pytest

from chipbench import delta_scan_need, spec

BENCH = spec.load_benchmark("BENCHMARK.json")
load = lambda name: spec.load_module(
    spec.find(BENCH, "layer_metrics", name)).read
ROOFLINE = load("kernel.delta_scan_roofline.py")
SHARE = load("serve.prefill_scan_kernel_share.py")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
QWEN = spec.load_json(spec.find(BENCH, "configs",
                                "qwen3-next-80b-a3b-serve.json"))
KIMI = spec.load_json(spec.find(BENCH, "configs",
                                "kimi-linear-48b-a3b-serve.json"))


def test_the_need_of_one_layer_by_hand():
    """A 4,096 prefill of the hybrid cell's layer: 32 value heads of 128 x
    128 float32, 64 chunks."""
    # a chunk of a value head: k k^T, q k^T (2 C^2 Dk), the solve by
    # substitution over Dv + Dk columns (C^2 each), k_cum S, q S, k^T v_new
    # (2 C Dk Dv), within v_new (2 C^2 Dv)
    chunk_head = (2 * 2 * 64 * 64 * 128 + 64 * 64 * 256
                  + 3 * 2 * 64 * 128 * 128 + 2 * 64 * 64 * 128)
    assert chunk_head == 10_485_760
    # q and k as the issue counts them, a VALUE head each: 4 x 67 MB
    repeated = delta_scan_need.call(4096, 32, 32, 128, 128)
    big = 4 * 4096 * 32 * 128 * 4
    assert big == 268_435_456
    small = 2 * 4096 * 32 * 4 + 2 * 32 * 128 * 128 * 4   # g, beta; the state
    assert repeated["bytes"] == big + small == 273_678_336
    assert repeated["flops"] == 2048 * chunk_head == 21_474_836_480
    # as the layer has them, 16 KEY heads: what the reader counts
    one = delta_scan_need.call(4096, 16, 32, 128, 128)
    assert one["bytes"] == 3 * 4096 * 32 * 128 * 4 + small == 206_569_472
    assert one["flops"] == repeated["flops"]
    # memory bound: 0.252 ms against 0.109 ms
    assert one["bytes"] / 819e9 > 2 * one["flops"] / 197e12
    # a shorter bucket needs its share of the operands and all of the state
    half = delta_scan_need.call(2048, 16, 32, 128, 128)
    assert half["flops"] == one["flops"] / 2
    assert half["bytes"] == (one["bytes"] - 4_194_304) / 2 + 4_194_304


def _trace(*rows):
    return {"rows0": [(name, s, e) for name, s, e in rows]}


CALL = "delta_scan.%d (f32[1,4096,4096], f32[1,32,128,128])"


def test_the_share_is_least_seconds_over_traced_seconds():
    # two calls of 2.5 ms each
    trace = _trace((CALL % 4, 0, 2_500_000),
                   ("fusion.7 bf16[40960,2048]", 2_500_000, 2_900_000),
                   (CALL % 5, 3_000_000, 5_500_000))
    least = 206_569_472 / 819e9
    got = delta_scan_need.roofline_share(trace, 16, 32, 128, 128, PEAK)
    assert got == pytest.approx(100 * 2 * least / 5e-3)
    assert 10 < got < 10.2
    run = types.SimpleNamespace(trace=trace, peak=PEAK, counters={},
                                ctx=types.SimpleNamespace(config=QWEN))
    assert ROOFLINE(run) == pytest.approx(got)
    # each call by its own bucket: a 2,048 one beside a 4,096 one
    mixed = _trace((CALL % 4, 0, 2_500_000),
                   ("delta_scan.9 (f32[1,2048,4096], f32[1,32,128,128])",
                    3_000_000, 4_250_000))
    half = (206_569_472 - 4_194_304) / 2 + 4_194_304
    assert delta_scan_need.roofline_share(
        mixed, 16, 32, 128, 128, PEAK) == pytest.approx(
            100 * (206_569_472 + half) / 819e9 / 3.75e-3)


@pytest.mark.parametrize("trace", [
    _trace(("fusion.3 f32[1,32,64,64,64]", 0, 800_000),
           ("delta_step.4 (f32[96,32,128], f32[96,32,128,128])", 0, 9_000)),
    _trace(),
    {},
    _trace(("delta_scan.4", 0, 2_500_000)),
], ids=["no-row", "empty", "no-rows", "no-shape"])
def test_nothing_to_read_reads_nothing(trace):
    """The parent's trace holds no ``delta_scan`` row, the per-channel
    cell's neither: nothing is reported and nothing raises; nor for a row
    that does not say its positions."""
    assert delta_scan_need.roofline_share(trace, 16, 32, 128, 128,
                                          PEAK) is None
    run = types.SimpleNamespace(trace=trace, peak=PEAK, counters={},
                                ctx=types.SimpleNamespace(config=QWEN))
    assert ROOFLINE(run) is None


def test_the_roofline_reader_needs_a_trace_a_peak_and_the_layers_keys():
    trace = _trace((CALL % 4, 0, 2_500_000))
    run = lambda **over: types.SimpleNamespace(**dict(dict(
        trace=trace, peak=PEAK, counters={},
        ctx=types.SimpleNamespace(config=QWEN)), **over))
    assert ROOFLINE(run()) is not None
    assert ROOFLINE(run(trace=None)) is None
    assert ROOFLINE(run(peak=None)) is None
    # Kimi Linear's file has no key and value heads apart, GPT-2's neither
    assert ROOFLINE(run(ctx=types.SimpleNamespace(config=KIMI))) is None
    assert ROOFLINE(run(ctx=types.SimpleNamespace(
        config={"hidden_size": 1600}))) is None


def _stats(scan):
    return types.SimpleNamespace(counters={"engine": {"prefill_scan": scan}})


def test_the_share_is_kernel_prefills_over_prefills():
    assert SHARE(_stats({"prefills": 143, "kernel_prefills": 143})) == 100.0
    assert SHARE(_stats({"prefills": 143, "kernel_prefills": 0})) == 0.0
    assert SHARE(_stats({"prefills": 8, "kernel_prefills": 2})) == 25.0


@pytest.mark.parametrize("scan", [
    None, {}, {"prefills": 0, "kernel_prefills": 0}],
    ids=["the-parent", "empty", "no-prefill"])
def test_a_program_without_the_counter_reads_nothing(scan):
    """The parent commit's ``stats()`` has no ``prefill_scan``: nothing is
    reported and nothing raises; nor for a window without a prefill."""
    assert SHARE(_stats(scan)) is None
    assert SHARE(types.SimpleNamespace(counters={})) is None
    assert SHARE(types.SimpleNamespace(counters={"engine": {}})) is None


@pytest.mark.parametrize("name, source, workloads", [
    ("serve.prefill_scan_kernel_share", "program_counter",
     ["serve-qwen3next-longdocs", "serve-kimilinear-reason"]),
    ("kernel.delta_scan_roofline", "device_trace",
     ["serve-qwen3next-longdocs"])])
def test_the_entries_are_as_the_issue_lists_them(name, source, workloads):
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert m == {"name": name, "unit": "%", "better": "higher",
                 "source": source, "layer": "kernels",
                 "moves": "serve_tokens_per_s", "workloads": workloads}
    assert set(workloads) <= {w["name"] for w in BENCH["workloads"]}

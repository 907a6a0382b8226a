"""``serve.loop_unnamed_share``, ``serve.loop_offcpu_share``, ``serve.gc_share``
and ``serve.decode_iter_p99_over_p50`` (PR 34): the readers over the engine's
``loop`` entry (the loop thread's clock), and their entries in
BENCHMARK.json."""

import types

import pytest

from chipbench import spec

BENCH = spec.load_benchmark("BENCHMARK.json")
NAMES = ("serve.loop_unnamed_share", "serve.loop_offcpu_share",
         "serve.gc_share", "serve.decode_iter_p99_over_p50")
READ = {name: spec.load_module(spec.find(BENCH, "layer_metrics",
                                         name + ".py")).read
        for name in NAMES}
DOCS = ["serve-gpt2xl-docs", "serve-olmoe-docs", "serve-qwen3next-longdocs",
        "serve-kimik2-agent"]


def _summary(count, p50, p99):
    return {"count": count, "mean": p50, "max": p99, "p50": p50, "p95": p99,
            "p99": p99}


def _record(step, wall, phase):
    return {"step": step, "at": 1.5, "wall": wall, "phase": phase,
            "by_phase": {phase: 0.9 * wall, "sweep": 0.01 * wall},
            "unnamed": 0.09 * wall, "wait": 0.0, "cpu": 0.004,
            "cpu_others": 0.9, "offcpu": wall - 0.004, "gc": 0.0,
            "switches": 212, "voluntary_switches": 3, "major_faults": 0}


def _loop(decode_p50=0.0125, decode_p99=0.015, decode_n=1000):
    return {"iterations": {"prefill": 300, "decode": decode_n, "idle": 0},
            "wall_s": 29.7, "covered_s": 29.4, "unnamed_s": 0.3,
            "wait_s": 22.0, "cpu_s": 6.2, "cpu_others_s": 9.0,
            "offcpu_s": 1.5, "gc_s": 0.6, "gc_collections": [400, 30, 2],
            "iteration": {"prefill": _summary(300, 0.06, 0.07),
                          "decode": _summary(decode_n, decode_p50,
                                             decode_p99),
                          "idle": _summary(0, 0.0, 0.0)},
            "longest": {"prefill": [_record(17, 0.08, "prefill.dispatch")],
                        "decode": [_record(8812, 3.12, "decode.emit"),
                                   _record(90, 0.15, "decode.readback")],
                        "idle": []}}


def _run(loop, window=(100.0, 130.0)):
    eng = {"loop": loop} if loop is not None else {"phases": {}}
    return types.SimpleNamespace(counters={"engine": eng}, window=window)


def test_fixed_numbers_in_the_four_values_out():
    run = _run(_loop())
    assert READ["serve.loop_unnamed_share"](run) == pytest.approx(1.0)
    assert READ["serve.loop_offcpu_share"](run) == pytest.approx(5.0)
    assert READ["serve.gc_share"](run) == pytest.approx(2.0)
    assert READ["serve.decode_iter_p99_over_p50"](run) == pytest.approx(1.2)


def test_the_shares_are_of_the_window_not_of_the_loops_wall():
    run = _run(_loop(), window=(0.0, 60.0))
    assert READ["serve.loop_offcpu_share"](run) == pytest.approx(2.5)


@pytest.mark.parametrize("name", NAMES)
def test_a_parent_without_the_clock_reads_nothing(name):
    assert READ[name](_run(None)) is None
    assert READ[name](types.SimpleNamespace(counters={},
                                            window=(0.0, 30.0))) is None


def test_a_window_with_no_decode_only_iteration_has_no_ratio():
    assert READ["serve.decode_iter_p99_over_p50"](
        _run(_loop(0.0, 0.0, 0))) is None


@pytest.mark.parametrize("name", ["serve.loop_offcpu_share",
                                  "serve.decode_iter_p99_over_p50"])
def test_the_longest_iterations_are_printed_with_their_records(name, capsys):
    READ[name](_run(_loop()))
    out = capsys.readouterr().out
    assert "decode step 8812 at 1.500 s: wall 3120.00 ms in decode.emit" \
        in out
    assert "off-cpu 3116.00" in out and "212 involuntary" in out
    assert "prefill step 17" in out and "decode step 90" in out
    assert "gc 0.600 s in [400, 30, 2] collections" in out
    assert "off-cpu 1.500 s" in out


def test_a_host_that_counts_no_switches_prints_none(capsys):
    loop = _loop()
    for kept in loop["longest"].values():
        for r in kept:
            for k in ("switches", "voluntary_switches", "major_faults"):
                del r[k]
    READ["serve.loop_offcpu_share"](_run(loop))
    out = capsys.readouterr().out
    assert "decode step 8812" in out and "switches" not in out


def test_the_entries_are_as_the_issue_lists_them():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    share = {"unit": "%", "better": "lower", "layer": "serving_loop",
             "moves": "serve_tokens_per_s", "workloads": DOCS}
    assert by_name["serve.loop_unnamed_share"] == dict(
        share, name="serve.loop_unnamed_share", source="program_span")
    assert by_name["serve.loop_offcpu_share"] == dict(
        share, name="serve.loop_offcpu_share", source="program_counter")
    assert by_name["serve.gc_share"] == dict(
        share, name="serve.gc_share", source="program_counter")
    assert by_name["serve.decode_iter_p99_over_p50"] == {
        "name": "serve.decode_iter_p99_over_p50", "unit": "ratio",
        "better": "lower", "source": "program_span",
        "layer": "serving_loop", "moves": "itl_p95_ms",
        "workloads": ["serve-gpt2xl-chat"]}

"""The reduction from trace rows to numbers: interval arithmetic on rows made
by hand, then the recorded slice of a real chip run (tests/data)."""

import os

import pytest

from chipbench import trace_reduce as tr


def test_merge_total_subtract():
    merged = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert merged == [[0, 3], [5, 8]]
    assert tr.total(merged) == 6
    assert tr.subtract([[0, 10]], merged) == [[3, 5], [8, 10]]
    assert tr.subtract([[0, 3], [4, 9]], [[1, 2], [2, 5], [8, 20]]) == [
        [0, 1], [5, 8]]
    assert tr.subtract([[0, 3]], []) == [[0, 3]]


def test_labels_are_the_instruction_name_and_result_type():
    hlo = ("%copy.391.remat = bf16[32,1024,25,64]{3,2,1,0:T(8,128)(2,1)} "
           "copy(bf16[32,1024,25,64]{1,3,2,0:T(8,128)(2,1)S(1)} "
           "%custom-call.196)")
    assert tr.label(hlo) == "copy.391.remat bf16[32,1024,25,64]"
    assert tr.op_kind(tr.label(hlo)) == "copy bf16[32,1024,25,64]"
    tup = ("%jvp_flash_fwd_.3 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, "
           "f32[128,1024,1]{2,1,0}) custom-call(bf16[128,1024,64]{2,1,0} %x)")
    assert tr.label(tup) == ("jvp_flash_fwd_.3 (bf16[128,1024,64], "
                             "f32[128,1024,1])")
    assert tr.op_kind("all-reduce-start.2 f32[8]") == "all-reduce-start f32[8]"
    assert tr.op_kind("fusion") == "fusion"
    assert tr.is_collective("all-reduce.1 f32[4]")
    assert not tr.is_collective("copy.3 f32[4]")
    # a consumer of a kernel's output is not the kernel
    r = {"rows0": [("jvp_flash_fwd_.3 (bf16[8])", 0, 5),
                   ("fusion.9 bf16[8] jvp_flash_fwd_.3", 5, 9)]}
    assert tr.kernel(r, ("flash_fwd",)) == (pytest.approx(5e-9), 1)


def test_reduce_rows_made_by_hand():
    us = 1000   # rows are in ns
    dev0 = [("fusion.1", 0 * us, 10 * us), ("flash_fwd.2", 10 * us, 30 * us),
            ("all-reduce-start.1", 30 * us, 31 * us),
            ("fusion.2", 31 * us, 41 * us),         # hides part of the reduce
            ("all-reduce-done.1", 41 * us, 60 * us),
            ("all-gather.3", 70 * us, 80 * us),     # synchronous, all exposed
            ("fusion.3", 90 * us, 100 * us)]
    dev1 = [("fusion.1", 0, 50 * us)]
    spans = [("trace_window", 0, 100 * us), ("train_step", 0, 70 * us),
             ("block", 55 * us, 68 * us), ("batch", 70 * us, 88 * us)]
    r = tr.reduce({"devices": {0: dev0, 1: dev1}, "spans": spans})
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy0_s"] == pytest.approx(80e-6)
    assert r["busy_s"] == pytest.approx((80e-6 + 50e-6) / 2)
    # collectives on device 0: [30, 60] and [70, 80]; fusion.2 hides 10
    assert r["collective_s"] == pytest.approx((40e-6 + 0) / 2)
    assert r["collective_exposed_s"] == pytest.approx((30e-6 + 0) / 2)
    assert r["op_seconds"]["fusion"] == pytest.approx(30e-6)
    assert tr.kernel(r, ("flash_fwd",)) == (pytest.approx(20e-6), 1)
    # idle on device 0: [60, 70] and [80, 90].  block (55-68) began after
    # train_step (0-70) and names 60-68, train_step 68-70, batch (70-88)
    # 80-88, and nothing was open in 88-90
    assert r["idle_by_span"] == {"block": pytest.approx(8e-6),
                                 "train_step": pytest.approx(2e-6),
                                 "batch": pytest.approx(8e-6),
                                 "no span": pytest.approx(2e-6)}
    b = tr.breakdown(r)
    assert b["device_ops"][:2] == [["fusion", pytest.approx(30e-6)],
                                   ["flash_fwd", pytest.approx(20e-6)]]
    assert sorted(b["idle_gaps"])[:2] == [["batch", pytest.approx(8e-6)],
                                          ["block", pytest.approx(8e-6)]]


def test_no_device_rows_reduce_to_nothing():
    assert tr.reduce({"devices": {}, "spans": [("trace_window", 0, 5)]}) == {}


# -- a slice of a real chip run ------------------------------------------------
# tests/data/train_1chip_slice.xplane.pb: 35 ms around a step boundary of the
# traced run of the one-chip training cell (my chip run, PR 22; TPU v5 lite),
# cut by tests/cut_trace.py.  The numbers below are those the reduction gave
# on the same 35 ms of the uncut 19 MB trace.

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def train_slice():
    return tr.reduce(tr.load(os.path.join(
        DATA, "train_1chip_slice.xplane.pb")))


def test_recorded_slice_busy_and_idle(train_slice):
    r = train_slice
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.035, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.029919538, abs=2e-9)
    assert r["busy0_s"] == r["busy_s"]
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0


def test_recorded_slice_idle_time_by_host_span(train_slice):
    got = train_slice["idle_by_span"]
    want = {"train_step": 0.002573832, "no span": 0.0015725,
            "device_put": 0.00065582, "batch": 0.00025371, "block": 2.46e-05}
    assert got == {k: pytest.approx(v, abs=2e-9) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(
        train_slice["window_s"] - train_slice["busy_s"], abs=1e-8)


def test_recorded_slice_kernels_by_name(train_slice):
    assert tr.kernel(train_slice, ("flash_bwd_dq",)) == (
        pytest.approx(0.001169481, abs=1e-9), 2)
    fwd_s, fwd_n = tr.kernel(train_slice, ("flash_fwd",))
    assert (fwd_n, fwd_s) == (6, pytest.approx(0.002621167, abs=1e-9))
    assert tr.kernel(train_slice, ("fused_ce_fwd", "fused_ce_bwd")) == (0, 0)
    top = tr.breakdown(train_slice)["device_ops"]
    assert len(top) == 10 and top[0] == [
        "convert_reduce_fusion (f32[8,1024], bf16[8,1024,1024])",
        pytest.approx(0.004639349, abs=1e-9)]
    assert top[1][0] == "jvp_flash_fwd_ (bf16[128,1024,64], f32[128,1024,1])"


def test_recorded_four_chip_slice_collectives():
    """tests/data/train_dp4_slice.xplane.pb: 14 ms of the backward pass of the
    four-chip training cell's traced run (my chip run, PR 22), all four
    devices.  The gradient all-reduces are synchronous operations on the
    core's own line, so nothing overlaps them: all of their time is exposed."""
    r = tr.reduce(tr.load(os.path.join(DATA, "train_dp4_slice.xplane.pb")))
    assert r["devices"] == 4
    assert r["window_s"] == pytest.approx(0.014, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.01399945, abs=2e-9)
    assert r["collective_s"] == pytest.approx(0.00679065, abs=2e-9)
    assert r["collective_exposed_s"] == r["collective_s"]
    assert r["idle_by_span"] == {"train_step": pytest.approx(5.47e-7, abs=1e-9)}
    top = tr.breakdown(r)["device_ops"][0]
    assert top[0].startswith("all-reduce (f32[1024,4096], f32[4096,1024]")
    assert top[1] == pytest.approx(0.004583942, abs=1e-9)
    assert tr.kernel(r, ("flash_bwd_dkv",)) == (
        pytest.approx(0.000820942, abs=1e-9), 1)

"""chipbench.scope_reduce: the wire-format reader on a trace encoded by hand,
scope paths taken apart, the reduction on rows made by hand, then the
recorded slice of this PR's chat trace (tests/data/chat_scoped_slice), which
keeps the program's ``td/`` annotations and every operation's scope path."""

import os
import struct

import pytest

from chipbench import scope_reduce as sr
from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# -- a trace encoded by hand (xplane.proto's field numbers) --------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(num, n):
    return _varint(num << 3) + _varint(n)


def _ld(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _entry(num, key, message):           # one map<int64, message> entry
    return _ld(num, _int(1, key) + _ld(2, message))


def _event(mid, off_ps, dur_ps):
    return _ld(4, _int(1, mid) + _int(2, off_ps) + _int(3, dur_ps))


def _by_hand(path):
    stats = (_entry(5, 1, _int(1, 1) + _ld(2, "tf_op"))
             + _entry(5, 2, _int(1, 2) + _ld(2, "program_id"))
             + _entry(5, 3, _int(1, 3) + _ld(2, "flops"))
             + _entry(5, 9, _int(1, 9) + _ld(2, "jit(f)/decode/head/dot:")))
    fusion = (_int(1, 1) + _ld(2, "%fusion.7 = bf16[8,4]{1,0:T(8,128)} "
                                  "fusion(bf16[8]{0} %p.1), kind=kLoop")
              + _ld(5, _int(1, 1) + _ld(5, "jit(step)/transpose(jvp(block0))"
                                           "/attn/dot_general:"))
              + _ld(5, _int(1, 2) + _int(3, 77))
              + _ld(5, _int(1, 3) + _varint(2 << 3 | 1)
                    + struct.pack("<d", 2.5)))
    copy = _int(1, 2) + _ld(2, "%copy.3 = bf16[4]{0} copy(bf16[4]{0} %x)")
    ref = (_int(1, 3) + _ld(2, "%dot.1 = f32[2]{0} dot()")
           + _ld(5, _int(1, 1) + _int(7, 9)))        # tf_op by reference
    device = (_int(1, 5) + _ld(2, "/device:TPU:0") + stats
              + _entry(4, 1, fusion) + _entry(4, 2, copy) + _entry(4, 3, ref)
              + _ld(3, _ld(2, "XLA Ops") + _int(3, 1000)
                    + _event(2, 0, 3_000_000) + _event(1, 3_000_000, 5_000_000)
                    + _event(3, 9_000_000, 1_000_000))
              + _ld(3, _ld(2, "XLA Modules") + _int(3, 1000)
                    + _event(1, 0, 10_000_000)))
    host = (_int(1, 6) + _ld(2, "/host:CPU")
            + _entry(4, 1, _int(1, 1) + _ld(2, "cb/trace_window"))
            + _entry(4, 2, _int(1, 2) + _ld(2, "td/decode.dispatch"))
            + _entry(4, 3, _int(1, 3) + _ld(2, "td/stage.put"))
            + _entry(4, 4, _int(1, 4) + _ld(2, "Linearize"))
            + _ld(3, _ld(2, "python3") + _int(3, 1000)
                  + _event(1, 0, 10_000_000) + _event(2, 1_000_000, 2_000_000)
                  + _event(4, 0, 500_000))
            + _ld(3, _ld(2, "python3") + _int(3, 1001)
                  + _event(3, 0, 4_000_000)))
    with open(path, "wb") as f:
        f.write(_ld(1, device) + _ld(1, host) + _ld(4, "hostname"))


def test_the_wire_reader_on_a_trace_encoded_by_hand(tmp_path):
    path = str(tmp_path / "hand.xplane.pb")
    _by_hand(path)
    t = sr.load(path)
    # ns on ProfileData's scale: line timestamp + offset
    assert t["devices"] == {0: [
        ("copy.3 bf16[4]", 1000.0, 4000.0, "", None),
        ("fusion.7 bf16[8,4]", 4000.0, 9000.0,
         "transpose(jvp(block0))/attn/dot_general", 77),
        ("dot.1 f32[2]", 10000.0, 11000.0, "decode/head/dot", None)]}
    assert t["spans"] == [("trace_window", 1000.0, 11000.0, 1),
                          ("td/decode.dispatch", 2000.0, 4000.0, 1),
                          ("td/stage.put", 1001.0, 5001.0, 2)]
    assert sr.find(path) == path


def test_scope_paths_taken_apart():
    bwd = "transpose(jvp(block3))/attn/flash_bwd_dq/pallas_call"
    assert sr.module_path(bwd) == ["block3", "attn", "flash_bwd_dq",
                                   "pallas_call"]
    assert sr.direction(bwd) == "bwd"
    assert sr.direction("jvp(block3)/mlp/1/erf") == "fwd"
    assert sr.module_path("jvp(block3)/mlp/1/erf") == ["block3", "mlp", "1",
                                                       "erf"]
    assert sr.direction("optimizer/mul") == ""
    assert sr.module_path("jvp()/add") == ["add"]
    assert sr.has_program_scope("decode/block0/attn/attend/dot_general")
    assert sr.has_program_scope("optimizer/sqrt")
    # the primitive alone, an argument's name, nothing: no scope of ours
    for none in ("reduce", "cache['block7.attn']['k']", ""):
        assert not sr.has_program_scope(none), none


def test_reduce_rows_made_by_hand():
    us = 1000.0
    dev = [("copy.1 bf16[4]", 0 * us, 10 * us, "", 7),              # filled
           ("fusion.1 bf16[4]", 10 * us, 30 * us,
            "decode/block0/attn/attend/dot_general", 7),
           ("copy.2 bf16[4]", 30 * us, 40 * us,
            "cache['block0.attn']['k']", 7),                        # filled
           ("fusion.2 bf16[4]", 50 * us, 60 * us,
            "decode/block0/mlp/0/dot_general", 7),
           ("copy.9 bf16[4]", 60 * us, 65 * us, "", 8),   # no scoped follower
           ("fusion.3 f32[4]", 80 * us, 90 * us, "prefill/head/dot", 9)]
    spans = [("trace_window", 0, 100 * us, 3),
             ("engine.step", 0, 78 * us, 1),
             ("td/decode.dispatch", 2 * us, 45 * us, 1),
             ("td/decode.readback", 45 * us, 70 * us, 1),
             ("td/stage.put", 40 * us, 95 * us, 2)]     # another thread
    r = sr.reduce({"devices": {0: dev}, "spans": spans})
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy0_s"] == pytest.approx(65e-6)
    assert r["dispatch_threads"] == 1
    assert r["scope_seconds"] == {
        "": pytest.approx(15e-6),
        "decode/block0/attn/attend/dot_general": pytest.approx(20e-6),
        "cache['block0.attn']['k']": pytest.approx(10e-6),
        "decode/block0/mlp/0/dot_general": pytest.approx(10e-6),
        "prefill/head/dot": pytest.approx(10e-6)}
    assert r["filled_seconds"] == {
        "decode/block0/attn/attend/dot_general": pytest.approx(30e-6),
        "decode/block0/mlp/0/dot_general": pytest.approx(20e-6),
        "": pytest.approx(5e-6), "prefill/head/dot": pytest.approx(10e-6)}
    # idle: [40, 50] dispatch 5 + readback 5; [65, 80] readback 5,
    # engine.step 8, then nothing open on the dispatching thread (stage.put
    # is open throughout, on a thread that dispatches nothing); [90, 100]
    assert r["idle_by_span"] == {
        "td/decode.dispatch": pytest.approx(5e-6),
        "td/decode.readback": pytest.approx(10e-6),
        "engine.step": pytest.approx(8e-6), "no span": pytest.approx(12e-6)}
    s = sr.shares(r)
    assert s["serve.decode_attn_share"] == pytest.approx(100 * 20 / 65)
    assert s["serve.prefill_device_share"] == pytest.approx(100 * 10 / 65)
    assert s["no_program_scope_share"] == pytest.approx(100 * 25 / 65)
    f = sr.shares(r, "filled_seconds")
    assert f["serve.decode_attend_share"] == pytest.approx(100 * 30 / 65)
    assert f["no_program_scope_share"] == pytest.approx(100 * 5 / 65)
    assert "train.fwd_share" not in s
    assert sr.by_module(r)[0] == ["decode/blockN", pytest.approx(30e-6)]
    assert sr.reduce({"devices": {}, "spans": []}) == {}


def test_training_shares_by_direction_and_module():
    r = {"busy0_s": 100.0, "scope_seconds": {
        "jvp(block0)/attn/dot_general": 10.0,
        "transpose(jvp(block0))/attn/flash_bwd_dq/pallas_call": 20.0,
        "jvp(block1)/mlp/1/erf": 5.0, "transpose(jvp(block1))/mlp/0/dot": 15.0,
        "jvp(block1)/ln2/rsqrt": 2.0, "transpose(jvp(ln_f))/mul": 3.0,
        "jvp(cast_params)/convert_element_type": 1.0,
        "transpose(jvp(loss))/fused_ce_bwd/pallas_call": 4.0,
        "optimizer/mul": 25.0, "grad_reduce/psum": 10.0, "reduce": 2.0,
        "": 3.0}}
    assert sr.shares(r) == {
        "train.fwd_share": pytest.approx(18.0),
        "train.bwd_share": pytest.approx(42.0),
        "train.optimizer_share": pytest.approx(25.0),
        "train.grad_reduce_share": pytest.approx(10.0),
        "model.attention_share": pytest.approx(30.0),
        "model.mlp_share": pytest.approx(20.0),
        "model.norm_share": pytest.approx(5.0),
        "no_program_scope_share": pytest.approx(5.0)}


# -- a slice of a real chip run ------------------------------------------------
# tests/data/chat_scoped_slice.xplane.pb: 80 ms of the chat cell's traced run
# (my chip run, PR 23; TPU v5 lite), cut by tests/cut_scoped_trace.py: the end
# of one decode iteration, an admission with its prefill, the start of the
# next iteration; a request staged on the staging thread meanwhile.  The
# numbers are those the reduction gave on the same 80 ms of the uncut 39 MB
# trace.

@pytest.fixture(scope="module")
def chat_slice():
    return sr.reduce(sr.load(os.path.join(
        DATA, "chat_scoped_slice.xplane.pb")))


def test_recorded_slice_idle_time_by_program_phase(chat_slice):
    r = chat_slice
    assert r["window_s"] == pytest.approx(0.08, abs=1e-9)
    assert r["busy0_s"] == pytest.approx(0.069826722, abs=2e-9)
    assert r["dispatch_threads"] == 1
    want = {"td/decode.dispatch": 0.002017410, "td/prefill.dispatch":
            0.001953704, "td/prefill.prepare": 0.001776026,
            "td/decode.readback": 0.001438162, "td/prefill.readback":
            0.001331923, "td/decode.emit": 0.00096851, "td/prefill.emit":
            0.00027194, "no span": 0.00017079, "engine.admit": 0.000131901,
            "engine.step": 9.463e-05, "td/sweep": 1.828e-05}
    got = r["idle_by_span"]
    assert got == {k: pytest.approx(v, abs=2e-9) for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(r["window_s"] - r["busy0_s"],
                                              abs=1e-8)
    # the staging thread's span is in the slice and names no gap
    spans = sr.load(os.path.join(DATA, "chat_scoped_slice.xplane.pb"))["spans"]
    assert [n for n, *_ in spans].count("td/stage.put") == 1
    assert "td/stage.put" not in got


def test_program_phases_name_what_the_harness_spans_only_bracket(chat_slice):
    """trace_reduce, which keeps ``cb/`` alone, puts the same idle time down
    to the two spans the harness wraps around the engine from outside."""
    old = tr.reduce(tr.load(os.path.join(
        DATA, "chat_scoped_slice.xplane.pb")))
    assert old["idle_by_span"] == {
        "engine.admit": pytest.approx(0.005467071, abs=2e-9),
        "engine.step": pytest.approx(0.004519592, abs=2e-9),
        "no span": pytest.approx(0.00018907, abs=2e-9)}
    assert old["busy0_s"] == pytest.approx(chat_slice["busy0_s"], abs=1e-5)
    bracketed = old["idle_by_span"]["engine.admit"] \
        + old["idle_by_span"]["engine.step"]
    named = sum(v for k, v in chat_slice["idle_by_span"].items()
                if k.startswith("td/"))
    assert named / bracketed == pytest.approx(0.979, abs=0.001)


def test_recorded_slice_shares_by_scope(chat_slice):
    s = sr.shares(chat_slice)
    # by its own op_name: the K/V pool's relayout copies carry none, or the
    # cache argument's
    assert s["no_program_scope_share"] == pytest.approx(73.1375, abs=1e-3)
    assert s["serve.decode_attn_share"] == pytest.approx(21.0337, abs=1e-3)
    assert s["serve.prefill_device_share"] == pytest.approx(4.9246, abs=1e-3)
    f = sr.shares(chat_slice, "filled_seconds")
    assert f["no_program_scope_share"] == pytest.approx(0.0268, abs=1e-3)
    assert f["serve.decode_attn_share"] == pytest.approx(90.6567, abs=1e-3)
    assert f["serve.decode_attend_share"] == pytest.approx(37.1761, abs=1e-3)
    assert f["serve.decode_cache_update_share"] == pytest.approx(36.2832,
                                                                abs=1e-3)
    assert f["serve.prefill_device_share"] == pytest.approx(7.6225, abs=1e-3)
    top = sr.by_module(chat_slice)
    assert [k for k, _ in top[:4]] == [
        "(no op_name)", "decode/blockN", "cache['blockN.attn']['k']",
        "cache['blockN.attn']['v']"]
    assert top[0][1] == pytest.approx(0.024950497, abs=1e-8)

"""tpu_dist.roles — role graphs, typed channels, per-role restart.

Tier-1 (`roles` marker): graph validation is pure units; channels run on
in-process TCPStore rigs (threads as "ranks"); the restart-policy units
spawn tiny jax-free scripts through spawn_graph; and THE acceptance e2e
spawns the full actor/learner example (4 actors + 1 learner), kills one
actor mid-run, and asserts the learner never stopped while the channel
resumed by name.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from tpu_dist.collectives.transport import DataPlane, FrameCorruptError
from tpu_dist.dist.store import TCPStore
from tpu_dist.roles import (Channel, ChannelClosedError, ChannelError,
                            ChannelPeerGoneError, ChannelSpec,
                            ChannelTimeoutError, Role, RoleGraph,
                            RoleGraphError, parse_roles_spec, spawn_graph)
from tpu_dist.roles.graph import down_key

pytestmark = pytest.mark.roles

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# graph spec validation
# ---------------------------------------------------------------------------


class TestGraph:
    def test_spans_and_accessors(self):
        g = RoleGraph([Role("learner", 1), Role("actor", 4)])
        assert g.world == 5
        assert list(g.span("learner")) == [0]
        assert list(g.span("actor")) == [1, 2, 3, 4]
        assert g.role_of(0) == ("learner", 0)
        assert g.role_of(3) == ("actor", 2)
        assert g.label(4) == "actor[3]"
        with pytest.raises(RoleGraphError, match="out of range"):
            g.role_of(5)

    def test_duplicate_role_names_named(self):
        with pytest.raises(RoleGraphError, match="duplicate role name"):
            RoleGraph([Role("a", 1), Role("a", 2)])

    def test_zero_world_named(self):
        with pytest.raises(RoleGraphError, match="positive world"):
            Role("a", 0)

    def test_bad_restart_policy_named(self):
        with pytest.raises(RoleGraphError, match="restart policy"):
            Role("a", 1, restart="sometimes")

    def test_bad_name_token_named(self):
        with pytest.raises(RoleGraphError, match="not a valid token"):
            Role("a:b", 1)

    def test_dangling_channel_endpoint_named(self):
        with pytest.raises(RoleGraphError, match="dangling endpoint"):
            RoleGraph([Role("a", 1), Role("b", 1)],
                      [ChannelSpec("c", src="a", dst="nope")])
        with pytest.raises(RoleGraphError, match="dangling endpoint"):
            RoleGraph([Role("a", 1)], [ChannelSpec("c", src="x", dst="a")])

    def test_duplicate_channel_name_named(self):
        with pytest.raises(RoleGraphError, match="duplicate channel"):
            RoleGraph([Role("a", 1), Role("b", 1)],
                      [ChannelSpec("c", "a", "b"),
                       ChannelSpec("c", "b", "a")])

    def test_spec_string_and_parse_roundtrip(self):
        g = RoleGraph([Role("learner", 1), Role("actor", 4, restart="solo")])
        assert g.spec_string() == "learner:1,actor:4:solo"
        g2 = parse_roles_spec(g.spec_string())
        assert [(r.name, r.world, r.restart) for r in g2.roles] == \
            [("learner", 1, "gang"), ("actor", 4, "solo")]

    @pytest.mark.parametrize("bad", ["", "a", "a:x", "a:1:often", "a:0",
                                     "a:1,,b:1", "a:1:solo:extra"])
    def test_bad_specs_raise(self, bad):
        with pytest.raises(RoleGraphError):
            parse_roles_spec(bad)

    def test_json_roundtrip_and_check_against(self):
        g = RoleGraph([Role("a", 2), Role("b", 1, restart="solo")],
                      [ChannelSpec("c", "a", "b", depth=3)])
        g2 = RoleGraph.from_json(g.to_json())
        assert g2.spec_string() == g.spec_string()
        assert g2.channel_spec("c").depth == 3
        g.check_against(g2)  # identical: fine
        with pytest.raises(RoleGraphError, match="disagrees"):
            g.check_against(RoleGraph([Role("a", 3), Role("b", 1)]))

    def test_subgroup_membership(self):
        g = RoleGraph([Role("learner", 1), Role("actor", 3)])
        sg = g.subgroup("actor", 2)
        assert sg.members == (1, 2, 3)
        assert sg.rank == 1 and sg.num_processes == 3
        # non-member view: collectives on it raise the named error
        sg0 = g.subgroup("actor", 0)
        assert sg0.rank is None
        # role-derived instance token: cannot collide with counter ids
        assert sg.group_id.endswith(".role-actor")


# ---------------------------------------------------------------------------
# channels (in-process rigs)
# ---------------------------------------------------------------------------


@pytest.fixture
def store():
    s = TCPStore(is_master=True)
    yield s
    s.close()


def _pair(store, name="ch", depth=4, gen=0, src=(1, 2), dst=(0,),
          dp_pair=None, kind="queue"):
    spec = ChannelSpec(name, src="prod", dst="cons", depth=depth, kind=kind)
    prod = Channel(spec, store, rank=src[0], role="prod",
                   src_span=list(src), dst_span=list(dst), generation=gen,
                   graph_world=3, dp=dp_pair[0] if dp_pair else False)
    cons = Channel(spec, store, rank=dst[0], role="cons",
                   src_span=list(src), dst_span=list(dst), generation=gen,
                   graph_world=3, dp=dp_pair[1] if dp_pair else False)
    return prod, cons


class TestChannel:
    def test_pytree_roundtrip_fifo(self, store):
        prod, cons = _pair(store)
        prod.put({"x": np.arange(5), "n": 7, "s": "hi"}, timeout=10)
        prod.put([np.ones(3)], timeout=10)
        out = cons.get(timeout=10)
        assert out["n"] == 7 and out["s"] == "hi"
        np.testing.assert_array_equal(out["x"], np.arange(5))
        np.testing.assert_array_equal(cons.get(timeout=10)[0], np.ones(3))

    def test_backpressure_bounded_depth(self, store):
        prod, cons = _pair(store, depth=2)
        prod.put(0, timeout=5)
        prod.put(1, timeout=5)
        landed = []
        t = threading.Thread(
            target=lambda: (prod.put(2, timeout=20), landed.append(1)))
        t.start()
        time.sleep(0.3)
        assert not landed, "3rd put must block at depth 2"
        assert cons.get(timeout=5) == 0
        t.join(10)
        assert landed
        assert cons.get(timeout=5) == 1 and cons.get(timeout=5) == 2

    def test_get_deadline_named_and_claim_released(self, store):
        prod, cons = _pair(store)
        with pytest.raises(ChannelTimeoutError, match="ch.*get.*prod"):
            cons.get(timeout=0.3)
        # single consumer: the timed-out claim was released, so the late
        # message is NOT skipped
        prod.put("late", timeout=5)
        assert cons.get(timeout=5) == "late"

    def test_put_deadline_named(self, store):
        prod, _cons = _pair(store, depth=1)
        prod.put(0, timeout=5)
        with pytest.raises(ChannelTimeoutError, match="backpressured"):
            prod.put(1, timeout=0.3)

    def test_closed_eof_after_drain(self, store):
        prod, cons = _pair(store, src=(1,))
        prod.put("a", timeout=5)
        prod.close()
        assert cons.get(timeout=5) == "a"  # in-queue survives the close
        with pytest.raises(ChannelClosedError, match="drained"):
            cons.get(timeout=5)

    def test_put_into_closed_consumer(self, store):
        prod, cons = _pair(store)
        cons.close()
        with pytest.raises(ChannelClosedError, match="no reader"):
            prod.put(1, timeout=5)

    def test_peer_death_named_with_roles_and_ranks(self, store):
        prod, cons = _pair(store)
        store.set(down_key(0, 1), b"1")
        store.set(down_key(0, 2), b"1")
        with pytest.raises(ChannelPeerGoneError) as ei:
            cons.get(timeout=20)
        assert ei.value.role == "prod" and ei.value.ranks == [1, 2]

    def test_mixed_closed_and_down_is_peer_death(self, store):
        prod, cons = _pair(store)
        prod.close()                      # rank 1 closed cleanly
        store.set(down_key(0, 2), b"1")   # rank 2 died
        with pytest.raises(ChannelPeerGoneError) as ei:
            cons.get(timeout=20)
        assert ei.value.ranks == [2]

    def test_latest_register_versions(self, store):
        prod, cons = _pair(store, kind="latest")
        assert cons.poll_latest(0) is None
        assert prod.put_latest({"w": 1}) == 1
        assert prod.put_latest({"w": 2}) == 2
        tree, ver = cons.get_latest(0, timeout=5)
        assert tree["w"] == 2 and ver == 2
        assert cons.poll_latest(ver) is None
        with pytest.raises(ChannelTimeoutError):
            cons.get_latest(ver, timeout=0.3)

    def test_generation_fencing_no_crosstalk(self, store):
        old, _ = _pair(store, gen=3)
        _, new = _pair(store, gen=4)
        old.put("stale", timeout=5)
        with pytest.raises(ChannelTimeoutError):
            new.get(timeout=0.4)  # a fresh generation never sees it

    def test_spec_mismatch_registration_named(self, store):
        _pair(store, name="reg", depth=4)
        spec2 = ChannelSpec("reg", src="prod", dst="cons", depth=9)
        with pytest.raises(ChannelError, match="does not match"):
            Channel(spec2, store, rank=1, role="prod", src_span=[1, 2],
                    dst_span=[0], generation=0, graph_world=3, dp=False)

    def test_wrong_role_endpoint_named(self, store):
        spec = ChannelSpec("w", src="prod", dst="cons")
        with pytest.raises(RoleGraphError, match="no endpoint"):
            Channel(spec, store, rank=0, role="bystander", src_span=[1],
                    dst_span=[0], generation=0, graph_world=2, dp=False)
        prod, cons = _pair(store, name="w2")
        with pytest.raises(RoleGraphError, match="consumer role"):
            prod.get(timeout=1)
        with pytest.raises(RoleGraphError, match="producer role"):
            cons.put(1, timeout=1)

    def test_store_payload_corruption_named(self, store):
        # netchaos `corrupt:surface=store` flips SET payload bytes in
        # transit; the sealed envelope then fails the consumer's CRC.
        # Deterministic equivalent here: corrupt the stored message
        # directly (the seal is the same _seal the store surface tests
        # pin, tests/test_netchaos.py::TestStoreSurface)
        prod, cons = _pair(store, name="crc")
        prod.put(np.arange(64), timeout=5)
        key = "tpu_dist/g0/roles/ch/crc/m/0"
        raw = bytearray(store.get(key))
        raw[len(raw) // 2] ^= 0x20
        store.set(key, bytes(raw))
        with pytest.raises(FrameCorruptError):
            cons.get(timeout=5)

    def test_decode_failure_acks_slot(self, store):
        # a corrupt message must not shrink the backpressure window: the
        # failed slot is still acked + deleted, so the channel keeps
        # flowing at full depth afterwards
        prod, cons = _pair(store, name="crcack", depth=2)
        prod.put("bad", timeout=5)
        prod.put("good", timeout=5)
        key = "tpu_dist/g0/roles/ch/crcack/m/0"
        raw = bytearray(store.get(key))
        raw[len(raw) // 2] ^= 0x20
        store.set(key, bytes(raw))
        with pytest.raises(FrameCorruptError):
            cons.get(timeout=5)
        # without the ack, head-acks == depth here and this put would
        # block out its deadline
        prod.put("after", timeout=2)
        assert cons.get(timeout=5) == "good"
        assert cons.get(timeout=5) == "after"
        assert cons.qsize() == 0

    def test_hole_skipped_after_settle(self, store, monkeypatch):
        # a producer killed between its head-claim and its message write
        # (solo-restart kill window) leaves a hole; the consumer must not
        # re-claim it forever — after the settle window it acks the hole
        # and the next get moves on to live messages
        monkeypatch.setenv("TPU_DIST_CH_HOLE_SETTLE", "0.2")
        prod, cons = _pair(store, name="hx")
        store.add("tpu_dist/g0/roles/ch/hx/head", 1)  # claim, no write
        with pytest.raises(ChannelTimeoutError, match="slot 0"):
            cons.get(timeout=0.3)  # first pass: plain timeout, claim back
        time.sleep(0.35)  # starve comfortably past the pinned settle
        with pytest.raises(ChannelTimeoutError, match="skipped a hole"):
            cons.get(timeout=0.3)  # healed: acked, claim consumed
        prod.put("after", timeout=5)
        assert cons.get(timeout=5) == "after"
        assert cons.qsize() == 0  # the hole was acked — window intact

    def test_multiconsumer_abandoned_claim_heals(self, store, monkeypatch):
        # a multi-consumer timed-out claim is abandoned (no sibling will
        # re-claim it) but NOT acked immediately: a producer still mid-
        # write gets its settle window, a late write is delivered by a
        # later get, and a true hole is acked once settled
        monkeypatch.setenv("TPU_DIST_CH_HOLE_SETTLE", "0.2")
        prod, cons = _pair(store, name="mc", src=(1,), dst=(0, 2))
        base = "tpu_dist/g0/roles/ch/mc"
        store.add(f"{base}/head", 1)        # slot 0 claimed, never written
        with pytest.raises(ChannelTimeoutError):
            cons.get(timeout=0.3)           # abandoned, not yet acked
        store.set(f"{base}/m/0", prod._encode("late", 0))
        assert cons.get(timeout=5) == "late"  # sweep delivers late write
        assert cons.qsize() == 0
        store.add(f"{base}/head", 1)        # slot 1: a true hole
        with pytest.raises(ChannelTimeoutError):
            cons.get(timeout=0.3)
        time.sleep(0.35)                    # starve past the settle
        prod.put("live", timeout=5)         # slot 2
        assert cons.get(timeout=5) == "live"  # sweep acked hole 1 first
        assert cons.qsize() == 0            # accounting intact

    def test_dp_recv_timeout_is_retryable(self, store, monkeypatch):
        # a data-plane recv timeout is transient (frames may still be in
        # flight): the single consumer must keep the envelope and release
        # its claim so the SAME slot delivers once the frames arrive —
        # unlike a corrupt seal, which is poison and gets acked away
        import pickle as pkl
        from tpu_dist.collectives.eager import _seal
        from tpu_dist.roles.channel import _DPRef
        monkeypatch.setenv("TPU_DIST_DP_THRESHOLD", str(16 * 1024))
        dps = [DataPlane(store, 1, 3), DataPlane(store, 0, 3)]
        try:
            prod, cons = _pair(store, name="rt", src=(1,),
                               dp_pair=(dps[0], dps[1]))
            a0 = np.arange(8192, dtype=np.float32)
            a1 = np.arange(8192, dtype=np.float32) * 2
            # the envelope put() would write, but with NO frames sent yet
            payload = pkl.dumps(({"src": 1, "dp": 2},
                                 [_DPRef(0), _DPRef(1)]),
                                protocol=pkl.HIGHEST_PROTOCOL)
            store.add("tpu_dist/g0/roles/ch/rt/head", 1)
            store.set("tpu_dist/g0/roles/ch/rt/m/0", _seal(payload))
            with pytest.raises(TimeoutError):
                cons.get(timeout=0.5)      # zero frames consumed
            assert store.check("tpu_dist/g0/roles/ch/rt/m/0"), \
                "envelope must survive a transient frame timeout"
            dps[0].send_array(0, "roles/ch/rt/0/0", a0)
            with pytest.raises(TimeoutError):
                cons.get(timeout=0.5)      # consumes frame 0, times out
            dps[0].send_array(0, "roles/ch/rt/0/1", a1)
            # the partially-received frame is HELD across the retry — a
            # re-claim must not livelock waiting for the consumed tag
            out = cons.get(timeout=10)
            np.testing.assert_array_equal(out[0], a0)
            np.testing.assert_array_equal(out[1], a1)
            assert cons.qsize() == 0
            # the retried message is counted ONCE (stats bump only after
            # a successful decode, not per attempt)
            assert cons.stats["dp_msgs"] == 1, cons.stats
        finally:
            for d in dps:
                d.close()

    def test_multiconsumer_unclaimed_timeout_not_lost(self, store,
                                                      monkeypatch):
        # an empty-queue multi-consumer timeout burns a claim on a slot NO
        # producer has claimed yet; the endpoint must remember it (settle
        # clock deferred until a producer claims it) so the eventual
        # message is delivered instead of orphaned
        monkeypatch.setenv("TPU_DIST_CH_HOLE_SETTLE", "0.2")
        prod, cons = _pair(store, name="mcu", src=(1,), dst=(0, 2))
        with pytest.raises(ChannelTimeoutError):
            cons.get(timeout=0.3)           # claims slot 0, head still 0
        time.sleep(0.35)                    # well past the settle floor
        prod.put("eventually", timeout=5)   # producer claims + writes 0
        assert cons.get(timeout=5) == "eventually"
        assert cons.qsize() == 0            # delivered and acked, no leak

    def test_reattach_clears_own_closed_marker(self, store):
        # a crashed producer's unwind posts its closed marker on the way
        # down; the solo respawn re-attaching by name must not keep
        # faking a clean EOF to the consumer
        spec = ChannelSpec("ra", src="prod", dst="cons")
        prod = Channel(spec, store, rank=1, role="prod", src_span=[1],
                       dst_span=[0], generation=0, graph_world=2, dp=False)
        cons = Channel(spec, store, rank=0, role="cons", src_span=[1],
                       dst_span=[0], generation=0, graph_world=2, dp=False)
        prod.close()                        # the crash-unwind close
        prod2 = Channel(spec, store, rank=1, role="prod", src_span=[1],
                        dst_span=[0], generation=0, graph_world=2,
                        dp=False)           # the respawned incarnation
        prod2.put("alive", timeout=5)
        assert cons.get(timeout=5) == "alive"  # no false EOF

    def test_consumer_killed_mid_get_claim_rewound_on_reattach(self, store):
        # the consumer twin of hole healing: an incarnation killed while
        # HOLDING a claim (rtail past acks) must not strand the message —
        # the respawned endpoint rewinds the orphaned claims at attach
        prod, cons = _pair(store, name="cr", src=(1,))
        prod.put("survives", timeout=5)
        store.add("tpu_dist/g0/roles/ch/cr/rtail", 1)  # died mid-get
        cons2 = Channel(cons.spec, store, rank=0, role="cons",
                        src_span=[1], dst_span=[0], generation=0,
                        graph_world=3, dp=False)       # the respawn
        assert cons2.get(timeout=5) == "survives"      # not skipped
        assert cons2.qsize() == 0                      # window intact

    def test_multiconsumer_killed_claims_inherited_by_respawn(
            self, store, monkeypatch):
        # the MPMC twin of the rewind above: multi-consumer claims cannot
        # be returned (a sibling may have claimed past), so each endpoint
        # persists its outstanding claims (claims/{rank}); an incarnation
        # killed while HOLDING one respawns into an endpoint that inherits
        # the claim into its abandoned ledger — a late write is delivered
        # and a true hole is settle-acked, never a leaked window
        monkeypatch.setenv("TPU_DIST_CH_HOLE_SETTLE", "0.2")
        prod, cons = _pair(store, name="mck", src=(1,), dst=(0, 2))
        base = "tpu_dist/g0/roles/ch/mck"
        store.add(f"{base}/head", 1)        # slot 0 claimed, never written
        with pytest.raises(ChannelTimeoutError):
            cons.get(timeout=0.3)           # claims slot 0...
        assert json.loads(store.get(f"{base}/claims/0").decode()) == [0]
        del cons                            # ...then SIGKILL: no unwind
        cons2 = Channel(prod.spec, store, rank=0, role="cons",
                        src_span=[1], dst_span=[0, 2], generation=0,
                        graph_world=3, dp=False)  # the respawn
        assert 0 in cons2._abandoned        # reconciled from the ledger
        store.set(f"{base}/m/0", prod._encode("late", 0))
        assert cons2.get(timeout=5) == "late"  # late write delivered
        assert cons2.qsize() == 0
        store.add(f"{base}/head", 1)        # slot 1: claimed, never written
        with pytest.raises(ChannelTimeoutError):
            cons2.get(timeout=0.3)          # claims slot 1, killed again
        cons3 = Channel(prod.spec, store, rank=0, role="cons",
                        src_span=[1], dst_span=[0, 2], generation=0,
                        graph_world=3, dp=False)  # second respawn
        assert 1 in cons3._abandoned
        prod.put("live", timeout=5)         # slot 2
        assert cons3.get(timeout=5) == "live"  # sweep arms hole-1 clock
        time.sleep(0.35)                    # starve past the settle
        with pytest.raises(ChannelTimeoutError):
            cons3.get(timeout=0.3)          # sweep acks the settled hole
        assert cons3.qsize() == 0           # window intact after two kills

    def test_crash_unwind_posts_no_eof_marker(self, store):
        # `with ch:` unwinding on an exception must NOT post the clean-EOF
        # marker — the supervisor may be about to solo-respawn this rank,
        # and peers must keep waiting for the respawn
        spec = ChannelSpec("cw", src="prod", dst="cons")
        prod = Channel(spec, store, rank=1, role="prod", src_span=[1],
                       dst_span=[0], generation=0, graph_world=2, dp=False)
        with pytest.raises(RuntimeError):
            with prod:
                raise RuntimeError("crash")
        assert not store.check("tpu_dist/g0/roles/ch/cw/closed/1")
        prod2 = Channel(spec, store, rank=1, role="prod", src_span=[1],
                        dst_span=[0], generation=0, graph_world=2, dp=False)
        with prod2:
            pass                            # clean exit DOES post EOF
        assert store.check("tpu_dist/g0/roles/ch/cw/closed/1")

    def test_context_channel_dp_conflict_named(self, store):
        from tpu_dist.roles.runtime import RoleContext
        g = RoleGraph([Role("prod", 1), Role("cons", 1)],
                      channels=[ChannelSpec("c", src="prod", dst="cons")])
        ctx = RoleContext(g, 0, store, 0, owns_store=False,
                          installed_rdzv=False)
        ch = ctx.channel("c", dp=False)
        assert ctx.channel("c", dp=False) is ch  # same wiring: cached
        assert ctx.channel("c") is ch            # default: cached
        with pytest.raises(RoleGraphError, match="re-wired"):
            ctx.channel("c", dp=object())        # conflicting dp: named

    def test_dataplane_path_roundtrip_and_stats(self, store, monkeypatch):
        monkeypatch.setenv("TPU_DIST_DP_THRESHOLD", str(16 * 1024))
        dps = [DataPlane(store, 1, 3), DataPlane(store, 0, 3)]
        try:
            prod, cons = _pair(store, name="dp", src=(1,),
                               dp_pair=(dps[0], dps[1]))
            big = np.random.default_rng(0).standard_normal(
                50_000).astype(np.float32)
            prod.put({"big": big, "small": np.arange(4), "m": "x"},
                     timeout=15)
            out = cons.get(timeout=15)
            np.testing.assert_array_equal(out["big"], big)
            assert out["m"] == "x"
            assert prod.stats["dp_msgs"] == 1 and \
                prod.stats["dp_leaves"] == 1, prod.stats
            assert cons.stats["dp_msgs"] == 1, cons.stats
        finally:
            for dp in dps:
                dp.close()

    def test_dataplane_frame_corruption_named(self, store, monkeypatch):
        # netchaos tcp cell: a bit flipped on the wire inside the big
        # leaf's frame surfaces as the transport's named FrameCorruptError
        from tpu_dist.resilience import netchaos
        monkeypatch.setenv("TPU_DIST_DP_THRESHOLD", str(16 * 1024))
        # pin the payload to inline TCP: in-process rigs are co-located,
        # and an SHM-lane payload is the `shm` netchaos surface, not `tcp`
        monkeypatch.setenv("TPU_DIST_SHM", "0")
        dps = [DataPlane(store, 1, 3), DataPlane(store, 0, 3)]
        try:
            prod, cons = _pair(store, name="dpc", src=(1,),
                               dp_pair=(dps[0], dps[1]))
            netchaos.install("corrupt:surface=tcp,rank=1,frame=1")
            prod.put(np.ones(50_000, np.float32), timeout=15)
            with pytest.raises(FrameCorruptError):
                cons.get(timeout=15)
        finally:
            netchaos.uninstall()
            for dp in dps:
                dp.close()


# ---------------------------------------------------------------------------
# obs / sanitizer role keying
# ---------------------------------------------------------------------------


class TestRoleKeying:
    def test_render_tail_includes_role(self):
        from tpu_dist.obs.hooks import render_tail
        line = render_tail({"coll": 4, "op": "all_reduce", "outcome": "ok",
                            "seq": 9, "events": 10, "role": "actor[2]"})
        assert "role=actor[2]" in line

    def test_recorder_dump_carries_role(self, tmp_path, monkeypatch):
        monkeypatch.setenv("TPU_DIST_ROLE", "learner")
        monkeypatch.setenv("TPU_DIST_ROLE_RANK", "0")
        from tpu_dist.obs.recorder import FlightRecorder
        rec = FlightRecorder(capacity=8, rank=0, world=1, generation=0)
        rec.record("collective", "all_reduce", coll=0)
        path = rec.dump("test", dir=str(tmp_path))
        doc = json.load(open(path))
        assert doc["role"] == "learner" and doc["role_rank"] == 0
        assert rec.last_position()["role"] == "learner[0]"

    def test_sanitizer_signs_role_on_flat_group(self, store, monkeypatch):
        monkeypatch.setenv("TPU_DIST_SANITIZE_TIMEOUT", "10")
        from tpu_dist.analysis.sanitizer import (CollectiveMismatchError,
                                                 check_collective, reset)
        from tpu_dist.roles.graph import clear_current, set_current

        class _G:
            def __init__(self, rank):
                self.rank, self.num_processes = rank, 2

        g = RoleGraph([Role("learner", 1), Role("actor", 1)])
        reset()
        errs = []

        def rank0():
            set_current(g, "learner", 0)
            try:
                check_collective(_G(0), store, "all_reduce",
                                 value=np.zeros(2), reduce_op="sum")
            except CollectiveMismatchError as e:
                errs.append(e)

        t = threading.Thread(target=rank0)
        t.start()
        time.sleep(0.4)   # rank 0's signature (role learner) is posted
        # the seq counter is process-local and the thread's call consumed
        # #0 — reset so this in-process "rank 1" posts at the SAME seq
        reset()
        set_current(g, "actor", 0)
        try:
            with pytest.raises(CollectiveMismatchError) as ei:
                check_collective(_G(1), store, "all_reduce",
                                 value=np.zeros(2), reduce_op="sum")
            msg = str(ei.value)
            assert "role" in msg and "learner" in msg and "actor" in msg
            t.join(10)
            assert errs and "role" in str(errs[0])
        finally:
            clear_current()
            reset()

    def test_sanitizer_deadline_names_missing_roles(self, store,
                                                    monkeypatch):
        from tpu_dist.analysis.sanitizer import (CollectiveMismatchError,
                                                 check_collective, reset)
        from tpu_dist.roles.graph import clear_current, set_current

        class _G:
            rank, num_processes = 0, 2

        monkeypatch.setenv("TPU_DIST_SANITIZE_TIMEOUT", "0.5")
        g = RoleGraph([Role("learner", 1), Role("actor", 1)])
        set_current(g, "learner", 0)
        reset()
        try:
            with pytest.raises(CollectiveMismatchError) as ei:
                check_collective(_G(), store, "barrier")
            assert "actor[0]" in str(ei.value)  # the missing rank, by role
        finally:
            clear_current()
            reset()


# ---------------------------------------------------------------------------
# spawn_graph restart policy (jax-free worker scripts — fast)
# ---------------------------------------------------------------------------


_POLICY_WORKER = textwrap.dedent("""
    import os, sys
    out, mode = sys.argv[1], sys.argv[2]
    rank = os.environ["RANK"]; role = os.environ["TPU_DIST_ROLE"]
    gen = os.environ["TPU_DIST_RESTART_COUNT"]
    inc = os.environ["TPU_DIST_ROLE_INCARNATION"]
    with open(os.path.join(out, f"r{rank}_g{gen}_i{inc}"), "w") as f:
        f.write(role)
    if mode == "solo-crash" and role == "w" \
            and os.environ["TPU_DIST_ROLE_RANK"] == "1" and inc == "0":
        sys.exit(3)
    if mode == "gang-crash" and role == "lead" and gen == "0":
        sys.exit(5)
""")


class TestSpawnGraphPolicy:
    def _run(self, tmp_path, mode, graph, **kw):
        script = tmp_path / "worker.py"
        script.write_text(_POLICY_WORKER)
        out = tmp_path / f"out_{mode}"
        out.mkdir()
        env_keep = dict(os.environ)
        try:
            os.environ["PYTHONPATH"] = _REPO + os.pathsep + \
                os.environ.get("PYTHONPATH", "")
            rc = spawn_graph(graph,
                             [sys.executable, str(script), str(out), mode],
                             restart_backoff=0.05, **kw)
        finally:
            os.environ.clear()
            os.environ.update(env_keep)
        return rc, sorted(p.name for p in out.iterdir())

    def test_solo_rank_restarts_alone_same_generation(self, tmp_path):
        g = RoleGraph([Role("lead", 1), Role("w", 2, restart="solo")])
        rc, runs = self._run(tmp_path, "solo-crash", g, solo_restarts=2)
        assert rc == 0
        # rank 2 (w[1]) ran twice IN GENERATION 0; nobody else re-ran
        assert runs == ["r0_g0_i0", "r1_g0_i0", "r2_g0_i0", "r2_g0_i1"]

    def test_gang_role_death_restarts_the_gang(self, tmp_path):
        g = RoleGraph([Role("lead", 1), Role("w", 2, restart="solo")])
        rc, runs = self._run(tmp_path, "gang-crash", g, max_restarts=1)
        assert rc == 0
        # the lead's death in generation 0 restarted EVERY rank into
        # generation 1 (fresh channel keyspace).  A worker the teardown
        # killed before its first line left no generation-0 file: the
        # launcher owes it none
        ran = {r for r in runs if r.endswith("_i0")}
        gen1 = {f"r{i}_g1_i0" for i in range(3)}
        assert gen1 | {"r0_g0_i0"} <= ran \
            <= gen1 | {f"r{i}_g0_i0" for i in range(3)}

    def test_budget_exhausted_returns_failing_rc(self, tmp_path):
        g = RoleGraph([Role("lead", 1)])
        rc, _ = self._run(tmp_path, "gang-crash", g, max_restarts=0)
        assert rc == 5

    def test_solo_budget_exhausted_fails_gang(self, tmp_path):
        # the crashing incarnation is ALWAYS 0 after a gang restart, so a
        # zero solo budget converts every crash into a gang round
        g = RoleGraph([Role("lead", 1), Role("w", 2, restart="solo")])
        rc, runs = self._run(tmp_path, "solo-crash", g, solo_restarts=0,
                             max_restarts=0)
        assert rc == 3


# ---------------------------------------------------------------------------
# the acceptance e2e: actor/learner with a mid-run actor kill
# ---------------------------------------------------------------------------


@pytest.mark.multiprocess
def test_solo_respawn_clears_stale_heartbeat(store):
    # a dead incarnation's last beat must not survive into the respawn:
    # the monitor would read the stale payload right after reset_rank and
    # demote the fresh incarnation from the startup grace to the plain
    # beat deadline — too short to boot, so it would be falsely lost
    from tpu_dist.resilience.heartbeat import HeartbeatMonitor, hb_key
    from tpu_dist.roles.launcher import _clear_stale_heartbeat
    store.set(hb_key(0, 1), b"999:5:7")  # dead incarnation's last beat
    mon = HeartbeatMonitor(store, 2, timeout=0.2, generation=0)
    assert mon.poll() == []              # picks the stale payload up
    time.sleep(0.3)
    assert [l.rank for l in mon.poll()] == [1]  # stale beat ages out
    _clear_stale_heartbeat(store, 0, 1)
    mon.reset_rank(1)
    time.sleep(0.3)
    assert mon.poll() == []              # fresh incarnation: full grace


def test_actor_learner_e2e_solo_restart_and_loss_decrease(tmp_path):
    """ISSUE 14 acceptance: 4 actors + 1 learner train end-to-end; chaos
    kills one actor mid-run; the supervisor restarts ONLY that actor (the
    learner's process and generation are uninterrupted) and the channel
    resumes by name — the restarted incarnation's batches reach the same
    queue and the learner consumes them.  Loss decreases."""
    out = tmp_path / "al"
    out.mkdir()
    obs_dir = tmp_path / "obsdumps"
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # armed flight recorder: every worker dumps its channel/store events
    # so the replay sanitizer can re-verify the protocol after the run
    env["TPU_DIST_OBS"] = "1"
    env["TPU_DIST_OBS_DIR"] = str(obs_dir)
    # kill actor[1] (global rank 2) at its 3rd produced batch — SIGKILL,
    # no teardown, exactly the preemption shape solo restart exists for
    env["TPU_DIST_CHAOS"] = "kill:rank=2,step=3"
    r = subprocess.run(
        [sys.executable, "-m", "tpu_dist.launch",
         "--roles", "learner:1,actor:4:solo", "--solo_restarts", "2",
         os.path.join(_REPO, "examples", "actor_learner.py"),
         "--actors", "4", "--max-steps", "100",
         "--out", str(out)],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"

    # (a) exactly one solo restart, of exactly rank 2, and NO gang round
    assert "role-solo-restart rank=2" in r.stderr, r.stderr
    assert "gang restart" not in r.stderr
    learner = json.load(open(out / "learner.json"))
    assert learner["generation"] == 0          # learner uninterrupted
    assert learner["steps"] == 100

    # (b) the channel resumed by name: the killed actor's SECOND
    # incarnation produced batches the learner consumed from the SAME
    # queue (actor role_rank 1 == global rank 2)
    i1 = json.load(open(out / "actor1_i1.json"))
    assert i1["incarnation"] == 1 and i1["produced"] >= 1
    assert 1 in learner["seen_incarnations"]["1"], \
        learner["seen_incarnations"]
    # undisturbed actors never respawned
    assert not (out / "actor0_i1.json").exists()

    # (c) training worked: loss decreased decisively head -> tail (Adam
    # 1e-3 / batch 64 reaches ~0.5 by step 100 on the synthetic set; the
    # 1.0 margin keeps batch-interleaving nondeterminism out of the gate)
    losses = learner["losses"]
    head = sum(losses[:10]) / 10
    tail = sum(losses[-10:]) / 10
    assert tail < head - 1.0, (head, tail)

    # (d) big batches rode the data plane, envelopes the sealed store
    assert learner["traj_stats"]["dp_msgs"] > 0, learner["traj_stats"]

    # (e) offline replay of the dumps re-verifies the channel protocol:
    # real put/claim/ack cursor events were recorded, and the SIGKILL +
    # solo restart left no accounting errors — no double-acked slot
    # (TD112) and no cross-generation store access (TD111).  The killed
    # incarnation leaves no dump, so its events are absent, not wrong.
    from tpu_dist import obs
    from tpu_dist.analysis import replay_dir
    dumps = obs.read_dumps(str(obs_dir))
    assert dumps, "no flight-recorder dumps written"
    ch_ops = {e.get("op") for d in dumps for e in d["events"]
              if e.get("kind") == "channel"}
    assert "put" in ch_ops and "claim" in ch_ops and "ack" in ch_ops, \
        ch_ops
    rep = replay_dir(str(obs_dir))
    errors = [f for f in rep.findings if f.severity == "error"
              and f.rule in ("TD111", "TD112")]
    assert not errors, [f.message for f in errors]


# ---------------------------------------------------------------------------
# bench smoke (tier-1 gate)
# ---------------------------------------------------------------------------


@pytest.mark.multiprocess
def test_bench_roles_smoke():
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_roles", "--smoke"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    rows = [json.loads(l) for l in r.stdout.splitlines() if l.strip()]
    cells = [x for x in rows if x["metric"] == "roles_channel_mb_s"]
    assert {c["path"] for c in cells} == {"store", "dataplane"}
    assert all(c["value"] > 0 for c in cells)
